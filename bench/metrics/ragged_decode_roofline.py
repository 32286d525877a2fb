"""The ``ragged_decode`` kernel's share of its roofline over the traced
window: the least time the chip needs for the work the decode attention
requires, over the kernel's device time.

The work of each call (one per attention layer and step) is counted from
valid lengths by the family's ``decode_attn_call``: for every live row,
q, the keys and values it may see at that layer (its own, and at a
selected layer its real shared prefix), and the output.  The least time
of a call is the larger of its FLOPs over the bf16 peak and its bytes
over the HBM bandwidth; at one query row per key the bytes bound it.
"""
import cells


def read(ctx):
    ops = ctx.trace.kernel_ops("_ragged_decode_step_jit")
    spent = sum(o.end - o.start for o in ops) / 1e9
    if not ops or spent <= 0:
        return None
    b = ctx.bench
    conf, sel = b.conf, set(b.layers)
    count = cells.family(conf).decode_attn_call
    need = 0.0
    for own, pfx, live in b.step_rows():
        for layer in range(b.cfg.attn_layer_count):
            keys = [int(o) + (int(p) if layer in sel else 0)
                    for o, p, a in zip(own, pfx, live) if a]
            if keys:
                f, n = count(conf, layer, keys)
                need += max(f / ctx.peak["bf16_flops_per_s"],
                            n / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * need / spent
