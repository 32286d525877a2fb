"""The window's wall time over its ragged iterations: the mean gap between
successive tokens of an in-flight request, admissions included (host
clock)."""


def read(ctx):
    return ctx.window_s / sum(w.stats["iterations"] for w in ctx.waves) * 1e3
