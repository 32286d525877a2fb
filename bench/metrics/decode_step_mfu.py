"""The ragged decode step's share of the chip's bf16 peak: the FLOPs the
traced steps' live rows require (``counts.decode_row_flops``) over the
step program's device time times the peak."""
import counts


def read(ctx):
    secs, calls = ctx.trace.module_seconds("_ragged_decode_step_jit")
    if not calls:
        return None
    b = ctx.bench
    M = len(b.layers)
    flops = sum(counts.decode_row_flops(b.conf, int(o), int(p), M)
                for own, pfx, live in b.step_rows()
                for o, p, a in zip(own, pfx, live) if a)
    return 100.0 * flops / (secs * ctx.peak["bf16_flops_per_s"])
