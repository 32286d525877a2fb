"""Generated tokens delivered over the window's wall time (host clock)."""


def read(ctx):
    return sum(w.stats["tokens"] for w in ctx.waves) / ctx.window_s
