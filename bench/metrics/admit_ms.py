"""Device time of admission, receiver prefill plus slot insert, per
admitted request (profiler trace)."""


def read(ctx):
    n = sum(len(w.requests) for w in ctx.waves)
    pre, calls = ctx.trace.module_seconds("_receiver_prefill_jit")
    ins, _ = ctx.trace.module_seconds("_insert_jit")
    if not calls or not n:
        return None
    return (pre + ins) / n * 1e3
