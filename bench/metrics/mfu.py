"""The traced window's share of the chip's bf16 peak: the FLOPs that the
work completed in it requires (sender prefills, receiver prefills and the
live rows of every decode step, counted by ``counts``) over the window's
length times the peak."""
import counts


def read(ctx):
    b = ctx.bench
    conf, M = b.conf, len(b.layers)
    flops = 0
    for w in ctx.waves:
        for r in w.requests:
            prefix = len(r.context) + 1
            flops += counts.sender_prefill_flops(conf, prefix)
            flops += counts.receiver_prefill_flops(conf, len(r.query),
                                                   prefix, M)
    flops += sum(counts.decode_row_flops(conf, int(o), int(p), M)
                 for own, pfx, live in b.step_rows()
                 for o, p, a in zip(own, pfx, live) if a)
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["bf16_flops_per_s"])
