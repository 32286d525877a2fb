"""Host gaps between decode steps, wave by wave, in one process on the chip.

    python3 bench/tools/hostgaps.py <cell> <waves> <seed>

Sets the cell up once, then serves ``<waves>`` of the window's waves and
prints one JSON line per wave: its wall time, the median and largest gaps
between successive calls of the receiver's ragged step (host clock), the
steps they fall after, and the time spent in shares.  A wave that runs long
with one or two gaps far above the rest, at steps where other waves have
none, has stalled on the host.  The benchmark's runs never call this.
"""
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import run  # noqa: E402  (sets the compilation cache up as a run does)


def main() -> None:
    name, waves, seed = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    run.persistent_cache()
    import cells
    import serve
    run.device_info(1)
    bench = serve.Bench(cells.load_cell(name), seed)
    bench.setup()
    rx, sess = bench.session.receiver, bench.session
    stamps, shares = [], []
    step, share = rx.ragged_step, sess.share

    def stamped_step(*a, **k):
        stamps.append(time.perf_counter())
        return step(*a, **k)

    def timed_share(*a, **k):
        t = time.perf_counter()
        out = share(*a, **k)
        shares.append(time.perf_counter() - t)
        return out

    rx.ragged_step, sess.share = stamped_step, timed_share
    for k in range(waves):
        stamps.clear()
        shares.clear()
        t0 = time.perf_counter()
        bench.sched.run(bench.wave_requests(k))
        wall = time.perf_counter() - t0
        gaps = np.diff(np.asarray(stamps)) * 1e3
        top = np.argsort(gaps)[-5:]
        print(json.dumps({
            "wave": k, "wall_s": wall, "steps": len(stamps),
            "gap_median_ms": float(np.median(gaps)),
            "top_gaps_ms": [float(gaps[i]) for i in top],
            "top_gaps_after_step": [int(i) for i in top],
            "share_s": sum(shares)}), flush=True)


if __name__ == "__main__":
    main()
