"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/limits.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--waves 1]

One process sets the cell up once, then for every seed serves ``--waves``
waves at the cell's own load (the same path and sizes as a run's window),
and compares a sample of what it served with the reference, as a run does.
For the control seeds it also reads the control: the reference computed
with float8 products in the program's place, judged by ``checks.verdict``
against the cell's own limits as a run's readings are.  The lower reading
of a limit is the largest program reading over the seeds, the upper one
the smallest control reading.  One JSON line per seed, with ``correct`` and
for a control seed ``control_correct``; the process exits non-zero where a
control came out correct.  The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]

import run  # noqa: E402  (sets the compilation cache up as a run does)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--waves", type=int, default=1)
    args = ap.parse_args()
    run.persistent_cache()
    import cells
    import checks
    import serve
    run.device_info(1)
    cell = cells.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    bench = serve.Bench(cell, seeds[0])
    bench.setup()
    lim = cell["check"]
    passed_controls = 0
    for seed in seeds:
        bench.seed = seed
        t = time.perf_counter()
        waves = []
        for k in range(args.waves):
            reqs = bench.wave_requests(k)
            comps, stats = bench.sched.run(reqs)
            waves.append(serve.Wave(reqs, comps, stats, 0.0, 0.0))
        served = time.perf_counter() - t
        picked = checks.sample(waves, seed, lim["requests"])
        t = time.perf_counter()
        r = checks.compare(bench, picked, control=seed in control)
        r.update(seed=seed, served_s=served,
                 compare_s=time.perf_counter() - t,
                 program_layers=list(bench.layers))
        r["correct"], r["checks"] = checks.verdict(r, lim["limits"])
        if "control" in r:
            r["control_correct"], r["control_checks"] = checks.verdict(
                r["control"], lim["limits"])
            passed_controls += r["control_correct"]
        print(json.dumps(r), flush=True)
    if passed_controls:
        print(f"{passed_controls} control(s) came out correct",
              file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
