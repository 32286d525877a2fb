"""The traced window's share of the chip's bf16 peak: the FLOPs that the
work completed in it requires (sender prefills, receiver prefills and the
live rows of every decode step, counted by the configuration's family)
over the window's length times the peak."""
import cells


def read(ctx):
    b = ctx.bench
    conf, fam = b.conf, cells.family(b.conf)
    flops = 0
    for w in ctx.waves:
        for r in w.requests:
            prefix = len(r.context) + 1
            flops += fam.sender_prefill_flops(conf, prefix)
            flops += fam.receiver_prefill_flops(conf, len(r.query),
                                                prefix, b.layers)
    flops += sum(fam.decode_row_flops(conf, int(o), int(p), b.layers)
                 for own, pfx, live in b.step_rows()
                 for o, p, a in zip(own, pfx, live) if a)
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["bf16_flops_per_s"])
