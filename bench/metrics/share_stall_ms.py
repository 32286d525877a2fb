"""Device idle time inside the harness's spans around ``CommSession.share``
per share: how long the chip waits while the int8 wire runs through the
host (profiler trace, host spans on the same clock)."""


def read(ctx):
    spans = ctx.trace.span_events("bench.share")
    if not spans:
        return None
    return ctx.trace.idle_within(spans) / len(spans) * 1e3
