"""The program's spans over traced windows of one cell, in one process on
the chip.

    python3 bench/tools/spantrace.py <cell> <windows> <seed> [<ops.json>]

Sets the cell up once as a run does, then traces ``<windows>`` windows of
the cell's ``trace_seconds``, each in a profile of its own, and prints one
JSON line per window: its wall time, iterations and tokens; each
request's time to first token split into its queue wait
(``Completion.queue_s``), its admission (to ``admitted_s``) and the rest,
as median and 90th percentile; every per-layer metric ``BENCHMARK.json``
lists for the cell, and the readers of the program's spans that it does
not list yet, over ``programspans.attach``; the share of the waves' device
idle that lies inside some program span; and that idle by innermost
program span.  A window that runs long shows which span held the host.
With ``<ops.json>``, one ragged step's device ops of the first window,
with their stats, are written there: where the kernel's name and the
named scopes appear.  The benchmark's runs never call this.
"""
import glob
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import numpy as np  # noqa: E402

import run  # noqa: E402  (sets the compilation cache up as a run does)

SPAN_METRICS = ("queue_wait_ms", "wire_encode_stall_ms",
                "wire_decode_stall_ms", "sched_host_stall_ms")


def step_ops(planes, module="_ragged_decode_step_jit", limit=400):
    """The ops of one execution of ``module`` (the middle one) on the
    first chip: their names and stats, in start order."""
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = [e for e in lines.get("XLA Modules", []) if module in e.name]
        if not mods:
            return {"lines": sorted(lines)}
        m = mods[len(mods) // 2]
        s, e = m.start_ns, m.start_ns + m.duration_ns
        ops = [{"name": ev.name, "duration_ns": ev.duration_ns,
                "stats": {k: str(v) for k, v in ev.stats}}
               for ev in lines.get("XLA Ops", [])
               if s <= ev.start_ns <= e][:limit]
        return {"lines": sorted(lines), "module": m.name,
                "module_stats": {k: str(v) for k, v in m.stats},
                "ops": ops}
    return {}


def ttft_split(waves):
    """Median and 90th percentile, in ms, of each request's queue wait,
    admission and the rest of its time to first token."""
    comps = [c for w in waves for c in w.completions]
    parts = {"queue": [c.queue_s for c in comps],
             "admission": [c.admitted_s - c.queue_s for c in comps],
             "rest": [c.ttft_s - c.admitted_s for c in comps]}
    return {k: [float(np.percentile(v, q)) * 1e3 for q in (50, 90)]
            for k, v in parts.items()}


def main() -> None:
    name, windows, seed = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    ops_out = sys.argv[4] if len(sys.argv) > 4 else None
    run.persistent_cache()
    import jax
    import cells
    import programspans
    import serve
    import tracereduce
    cell = cells.load_cell(name)
    device = run.device_info(cells.benchmark_entry(name)["chips"])
    peak = run.peak_of(device["kind"])
    watch = serve.CompileWatch()
    bench = serve.Bench(cell, seed)
    bench.setup()
    setup_s = time.perf_counter() - run.T_START
    bench.trace_on()
    listed = run.cell_metrics(name, True)
    specs = listed + [{"name": m, "unit": "ms"} for m in SPAN_METRICS
                      if m not in {x["name"] for x in listed}]
    trace_dir = os.path.join(run.OUT_DIR, "spantrace")
    for k in range(windows):
        shutil.rmtree(trace_dir, ignore_errors=True)
        bench.steps.clear()
        before = watch.snapshot()
        jax.profiler.start_trace(trace_dir)
        waves = bench.window(cell["trace_seconds"])
        jax.profiler.stop_trace()
        compiled = [a - b for a, b in zip(watch.snapshot(), before)]
        path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        planes = list(jax.profiler.ProfileData.from_file(path).planes)
        trace = programspans.attach(tracereduce.reduce_planes(planes),
                                    planes)
        if k == 0 and ops_out:
            with open(ops_out, "w") as f:
                json.dump(step_ops(planes), f, indent=1)
        ctx = tracereduce.Context(bench=bench, waves=waves, setup_s=setup_s,
                                  peak=peak, trace=trace)
        metrics = {m: v["value"]
                   for m, v in run.read_metrics(specs, ctx).items()}
        print(json.dumps({
            "window": k, "seed": seed, "setup_s": setup_s,
            "compiled": compiled,
            "window_s": waves[-1].end - bench.t0,
            "iterations": sum(w.stats["iterations"] for w in waves),
            "tokens": sum(w.stats["tokens"] for w in waves),
            "ttft_split_ms": ttft_split(waves),
            "busy_s": trace.busy_s, "trace_window_s": trace.window_s,
            "metrics": metrics,
            "program_span_coverage": programspans.coverage(trace),
            "idle": programspans.relabelled(trace).breakdown()["idle_gaps"],
            "bench_idle": trace.breakdown()["idle_gaps"]}), flush=True)
    shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
