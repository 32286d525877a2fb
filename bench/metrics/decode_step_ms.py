"""Device time per call of the ragged decode step program (profiler
trace)."""


def read(ctx):
    secs, calls = ctx.trace.module_seconds("_ragged_decode_step_jit")
    return secs / calls * 1e3 if calls else None
