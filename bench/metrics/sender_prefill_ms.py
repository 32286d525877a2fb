"""Device time per call of the sender's prefill program (profiler trace)."""


def read(ctx):
    secs, calls = ctx.trace.module_seconds("_sender_prefill_jit")
    return secs / calls * 1e3 if calls else None
