"""90th percentile of each request's wait before its admission starts, over
every request of the window: from its wave's submit, the origin of
``ttft_s``, to the start of its admission (``Completion.queue_s``, the
scheduler's own counter).  A program without the counter reads nothing."""
import numpy as np


def read(ctx):
    waits = [getattr(c, "queue_s", None) for w in ctx.waves
             for c in w.completions]
    if not waits or None in waits:
        return None
    return float(np.percentile(waits, 90)) * 1e3
