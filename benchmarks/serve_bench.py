"""Serving-path perf: the overlapped continuous-batching scheduler vs the
serial per-request reference loop (§Perf trajectory, serving iteration).

Both paths serve the SAME mixed-length request stream (contexts sampled
across fact counts, per-request generation budgets varied) over the trained
pair at each selection ratio:

  serial    : ``serve_serial`` — blocking share (synced transfer stamp) ->
              prefill -> per-token streamed decode, one request at a time;
  scheduled : ``repro.serving.scheduler.Scheduler`` — fixed-capacity slot
              table, one donated compiled ragged step per iteration over
              every in-flight request, admissions async-dispatched behind
              the running step (sender prefill overlaps receiver decode).

Token-for-token parity is asserted before timing (the speedup is only
interesting if the outputs are the same). Both paths are fully warmed (one
untimed pass) so the numbers are steady-state throughput, not compile time.

The scheduler is additionally run with ``decode_backend="pallas"`` (the
fused ragged-decode kernel) at each ratio — token parity with the serial
reference is asserted before its row is reported.

Writes ``BENCH_serve.json`` at the repo root: tokens/s (serial, scheduled
reference, scheduled pallas), TTFT p50, slot occupancy, speedup, per ratio
in {0.3, 0.5}.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from benchmarks import common
from repro.core.types import KVCommConfig
from repro.serving.scheduler import (Scheduler, SchedulerConfig,
                                     make_requests, serve_serial)

REQUESTS = int(os.environ.get("REPRO_SERVE_REQUESTS", "24"))
CAPACITY = int(os.environ.get("REPRO_SERVE_CAPACITY", "8"))
MAX_NEW = int(os.environ.get("REPRO_SERVE_MAX_NEW", "8"))
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")


def build_stream(tok):
    """Mixed lengths on every axis continuous batching cares about:
    ragged prefixes (fact counts 4/6/8), ragged generation budgets."""
    from repro.data.synthetic import SyntheticTask, TaskConfig
    per = -(-REQUESTS // 3)   # ceil: never bench fewer than configured
    batches = [SyntheticTask(tok, TaskConfig("retrieval", num_facts=nf,
                                             seed=1001 + i)).batch(per)
               for i, nf in enumerate((4, 6, 8))]
    reqs = make_requests(batches, max_new=MAX_NEW, pad=tok.PAD)[:REQUESTS]
    for i, r in enumerate(reqs):
        r.max_new = (MAX_NEW, max(MAX_NEW // 2, 1), MAX_NEW)[i % 3]
    return reqs


def bench_ratio(session, tok, ratio: float) -> dict:
    kvcfg = KVCommConfig(ratio=ratio, selector="prior_only")
    reqs = build_stream(tok)
    cfg_s = SchedulerConfig(capacity=CAPACITY)

    cfg_pal = SchedulerConfig(capacity=CAPACITY, decode_backend="pallas")

    # --- warm + parity gates (compiles every path end to end) ---
    ser, _ = serve_serial(session, reqs, kvcfg)
    sched = Scheduler(session, kvcfg, config=cfg_s)
    got, _ = sched.run(reqs)
    assert all(np.array_equal(a.tokens, b.tokens)
               for a, b in zip(ser, got)), \
        "scheduled output diverged from the serial reference"
    pal, _ = Scheduler(session, kvcfg, config=cfg_pal).run(reqs)
    assert all(np.array_equal(a.tokens, b.tokens)
               for a, b in zip(ser, pal)), \
        "pallas backend diverged from the serial reference"

    # --- timed passes (steady state) ---
    t0 = time.perf_counter()
    ser, ser_stats = serve_serial(session, reqs, kvcfg)
    serial_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    got, sch_stats = Scheduler(session, kvcfg, config=cfg_s).run(reqs)
    sched_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    pal, pal_stats = Scheduler(session, kvcfg, config=cfg_pal).run(reqs)
    pallas_s = time.perf_counter() - t0

    n_tok = ser_stats["tokens"]
    serial_tps = n_tok / serial_s
    sched_tps = n_tok / sched_s
    pallas_tps = n_tok / pallas_s
    return {
        "requests": len(reqs),
        "tokens": n_tok,
        "serial_tokens_per_s": round(serial_tps, 1),
        "scheduled_tokens_per_s": round(sched_tps, 1),
        "pallas_tokens_per_s": round(pallas_tps, 1),
        "speedup": round(sched_tps / serial_tps, 2),
        "pallas_vs_reference": round(pallas_tps / sched_tps, 2),
        "serial_ttft_ms_p50": round(
            float(np.median([c.ttft_s for c in ser])) * 1e3, 1),
        "scheduled_ttft_ms_p50": round(
            float(np.median([c.ttft_s for c in got])) * 1e3, 1),
        "slot_occupancy": round(sch_stats["occupancy"], 3),
        "parity": True,
        "pallas_parity": True,
    }


def run(emit=common.emit) -> dict:
    session, cfg, tok = common.make_session()
    out = {
        "config": {"requests": REQUESTS, "capacity": CAPACITY,
                   "max_new": MAX_NEW, "L": cfg.attn_layer_count,
                   "d_model": cfg.d_model},
        "ratios": {},
    }
    for ratio in (0.3, 0.5):
        # each ratio freezes a new selection -> fresh compiles; drop the
        # previous ratio's executables (interpret-mode pallas programs are
        # mmap-heavy)
        jax.clear_caches()
        r = bench_ratio(session, tok, ratio)
        out["ratios"][str(ratio)] = r
        emit(f"serve/ratio_{ratio}", 0.0,
             f"serial={r['serial_tokens_per_s']}tok/s;"
             f"sched={r['scheduled_tokens_per_s']}tok/s;"
             f"pallas={r['pallas_tokens_per_s']}tok/s;"
             f"x{r['speedup']};occ={r['slot_occupancy']}")
    out["speedup_at_0.3"] = out["ratios"]["0.3"]["speedup"]
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    print(json.dumps(run(), indent=1))
