"""The benchmark's one command: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``, from process start to the window): the
device check, weights made on the device from the configuration's seed,
calibration, and one pass over every shape the cell uses, with JAX's
persistent compilation cache at ``<checkout>/.jax_cache``.  The window then
serves closed waves for ``--seconds``; nothing compiles inside it (checked).
With ``--trace 1`` the window is profiled and the cell's per-layer metrics
are read from the trace; with ``--trace 0`` its end-to-end metrics are
taken on the host clock.  After the window the served tokens of a sample
of requests are compared with the plain reference (``checks.py``).

Every metric is read by ``metrics/<name>.py``; a cell is
``workloads/<cell>.json``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each compared number
beside its limit, which the last lines of standard error repeat.

There is no fallback: without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def persistent_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, for
    every program however fast it compiles, and never evicted."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"no {chips} TPU chip(s): found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_of(kind: str, require: bool = True) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        if require:
            raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
        return next(iter(peaks.values()))
    return peaks[kind]


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def cell_metrics(name: str, traced: bool) -> list:
    """The metrics this cell reports: end-to-end without a trace, the
    per-layer ones that list this cell (or list none) with one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if name in m.get("workloads", [name])]


def read_metrics(specs: list, ctx) -> dict:
    out = {}
    for m in specs:
        value = importlib.import_module(f"metrics.{m['name']}").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, *, cell: dict | None = None,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    if require_tpu:
        persistent_cache()
    import cells
    import checks
    import serve
    import tracereduce

    if cell is None:
        cell = cells.load_cell(args.workload)
        chips = cells.benchmark_entry(args.workload)["chips"]
    else:
        chips = 1
    device = device_info(chips, require_tpu)
    peak = peak_of(device["kind"], require_tpu)
    watch = serve.CompileWatch()

    bench = serve.Bench(cell, args.seed)
    bench.setup()
    setup_s = time.perf_counter() - T_START
    made = {"compiled": watch.compiled - watch.loaded, "loaded": watch.loaded}

    traced = bool(args.trace)
    seconds = min(args.seconds, cell["trace_seconds"]) if traced \
        else args.seconds
    trace_dir = os.path.join(OUT_DIR, "trace")
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        bench.trace_on()
        jax.profiler.start_trace(trace_dir)
    before = watch.snapshot()
    waves = bench.window(seconds)
    if traced:
        jax.profiler.stop_trace()
    compiled = [a - b for a, b in zip(watch.snapshot(), before)]
    if any(compiled):
        print(f"the window compiled: {compiled[0]} executables made, "
              f"{compiled[1]} program traces", file=sys.stderr)
        return 3
    device["memory_peak_bytes"] = memory_peak()

    n_req = sum(len(w.requests) for w in waves)
    done = sum(1 for w in waves for r, c in
               zip(sorted(w.requests, key=lambda r: r.rid), w.completions)
               if len(c.tokens) == r.max_new)
    ctx = tracereduce.Context(bench=bench, waves=waves, setup_s=setup_s,
                              peak=peak)
    breakdown = None
    if traced:
        trace = tracereduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace = trace
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        breakdown = trace.breakdown()
    metrics = read_metrics(cell_metrics(args.workload, traced), ctx)

    lim = cell["check"]
    picked = checks.sample(waves, args.seed, lim["requests"])
    t_ref = time.perf_counter()
    readings = checks.compare(bench, picked)
    ref_s = time.perf_counter() - t_ref
    correct, table = checks.verdict(readings, lim["limits"])

    print(json.dumps({
        "cell": args.workload, "seed": args.seed, "waves": len(waves),
        "requests": n_req, "completed": done,
        "ttft_samples": sum(len(w.completions) for w in waves),
        "iterations": sum(w.stats["iterations"] for w in waves),
        "tokens": sum(w.stats["tokens"] for w in waves),
        "window_s": waves[-1].end - bench.t0,
        "wire_bytes": bench.session.transport.total_bytes - bench.wire0,
        "setup_s": setup_s, "setup_phases": bench.phases,
        "setup_executables": made,
        "reference_s": ref_s, "reference_requests": readings["requests"],
        "reference_tokens": readings["served_tokens"],
        "selected_layers": list(bench.layers)}))
    for k, v in table.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    result = {"correct": correct, "attempted": n_req,
              "failed": n_req - done, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = table
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
