"""Benchmark entrypoint: one harness per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the repo convention and writes
per-benchmark JSON artifacts into experiments/.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig8,...]
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SUITES = [
    "serve_bench",         # continuous batching vs serial (BENCH_serve)
    "fig8_efficiency",     # paper Fig. 8 + §3.3 (analytic + measured)
    "table1_comm",         # paper Table 1
    "table2_random",       # paper Table 2 / 9
    "fig4_contiguous",     # paper Figs. 4-6
    "fig7_attention",      # paper Fig. 7 (H2)
    "fig11_calibration",   # paper Fig. 11 (§H)
    "table10_multisender", # paper Table 10 (§J)
    "table11_positional",  # paper Table 11 (§M)
    "roofline",            # EXPERIMENTS.md §Roofline (needs dryrun.json)
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of suites")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    wanted = args.only.split(",") if args.only else SUITES

    print("name,us_per_call,derived")
    failures = []
    for name in SUITES:
        if name not in wanted:
            continue
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}",
                  file=sys.stdout)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
