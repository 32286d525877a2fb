"""Mean share of the slot table's rows that were live per iteration, over
the traced waves (the scheduler's own counter, weighted by iterations)."""


def read(ctx):
    it = sum(w.stats["iterations"] for w in ctx.waves)
    if not it:
        return None
    return 100.0 * sum(w.stats["occupancy"] * w.stats["iterations"]
                       for w in ctx.waves) / it
