"""90th percentile of time to first token over every request of the
window: from its wave's submit to its first token on the host, the wait
for a slot included (host clock)."""
import numpy as np


def read(ctx):
    ttft = [c.ttft_s for w in ctx.waves for c in w.completions]
    return float(np.percentile(ttft, 90)) * 1e3
