"""The ragged decode step's share of the chip's bf16 peak: the FLOPs the
traced steps' live rows require (the family's ``decode_row_flops``)
over the step program's device time times the peak."""
import cells


def read(ctx):
    secs, calls = ctx.trace.module_seconds("_ragged_decode_step_jit")
    if not calls:
        return None
    b = ctx.bench
    count = cells.family(b.conf).decode_row_flops
    flops = sum(count(b.conf, int(o), int(p), b.layers)
                for own, pfx, live in b.step_rows()
                for o, p, a in zip(own, pfx, live) if a)
    return 100.0 * flops / (secs * ctx.peak["bf16_flops_per_s"])
