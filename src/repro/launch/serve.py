"""KVComm serving launcher: continuous-batching sender->receiver serving.

The serving driver the paper's deployment implies, on the ``repro.comm``
stack: a sender Agent holding contexts, a receiver Agent answering queries,
KV flowing between them through a byte-accounted Transport with calibrated,
per-task-frozen layer selection.

Default path is the overlapped continuous-batching scheduler
(``repro.serving.scheduler``): a fixed-capacity slot table decoding every
in-flight request per compiled ragged iteration, admissions (sender prefill
+ transfer + receiver prefill) async-dispatched behind the in-flight step.
``--serial`` keeps the pre-scheduler reference loop (blocking per-request
share -> stream). ``--transport serialized`` materializes the actual wire
payload; the wire defaults to int8 (characterized across the task suite in
``experiments/wire_codec.json`` — ``--wire-dtype float16`` restores the old
default).

    PYTHONPATH=src python -m repro.launch.serve --requests 32 --ratio 0.5

``--arch llama3.2-3b-pair`` serves that registry config at its published
widths in bfloat16 with seeded random weights (see ``pairs.seeded_pair``) —
the accelerator path; accuracy is then chance-level by construction.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.comm import (Agent, CommSession, InMemoryTransport,
                        SerializedTransport)
from repro.core.types import KVCommConfig
from repro.data.synthetic import SyntheticTask, TaskConfig
from repro.launch.pairs import load_pair
from repro.serving.scheduler import (Scheduler, SchedulerConfig, accuracy,
                                     make_requests, serve_serial)
from repro.utils.compile_cache import enable_compile_cache


def build_requests(tok, task: str, n: int, max_new: int,
                   context_tile: int = 1):
    """A mixed-length request stream: contexts sampled across fact counts
    so prefix lengths are ragged (what continuous batching is for).
    ``context_tile`` repeats each context that many times — long shared
    documents from the same generator, still ragged."""
    batches = []
    per = -(-n // 3)   # ceil: never serve fewer than asked
    for i, nf in enumerate((4, 6, 8)):
        t = SyntheticTask(tok, TaskConfig(task, num_facts=nf, seed=42 + i))
        batches.append(t.batch(per))
    reqs = make_requests(batches, max_new=max_new, pad=tok.PAD)[:n]
    for r in reqs:
        r.context = np.tile(r.context, context_tile)
    return reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--ratio", type=float, default=0.5)
    ap.add_argument("--alpha", type=float, default=0.7)
    ap.add_argument("--task", default="retrieval",
                    choices=["retrieval", "multihop", "decision"])
    # (no --method: the serving scheduler IS the kvcomm KV-sharing path;
    # the other registered CommMethods remain reachable via
    # CommSession.run and the benchmark harness)
    ap.add_argument("--transport", default="inmemory",
                    choices=["inmemory", "serialized"])
    ap.add_argument("--wire-dtype", default="int8",
                    choices=["float16", "bfloat16", "float32", "int8"])
    ap.add_argument("--serial", action="store_true",
                    help="pre-scheduler reference: blocking per-request "
                         "share -> streamed decode")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=8,
                    help="slot-table rows (in-flight requests)")
    ap.add_argument("--decode-backend", default="reference",
                    choices=["reference", "pallas"],
                    help="per-step decode attention: masked-dense "
                         "reference or the fused Pallas ragged kernel "
                         "(interpret-mode off-TPU)")
    ap.add_argument("--arch", default=None,
                    help="registry config served at full width with seeded "
                         "weights (e.g. llama3.2-3b-pair); default: the "
                         "trained CPU stand-in pair")
    args = ap.parse_args()

    enable_compile_cache()
    cfg, tok, sender, receiver = load_pair(args.arch)
    transport = (SerializedTransport(args.wire_dtype)
                 if args.transport == "serialized" else InMemoryTransport())
    session = CommSession(Agent("sender", cfg, sender, tok),
                          Agent("receiver", cfg, receiver, tok),
                          transport)
    task = SyntheticTask(tok, TaskConfig(args.task, num_facts=6, seed=42))

    # one-sample calibration (paper §H), then the selection is frozen
    # under the task key for every subsequent request
    calib = task.batch(1)
    scores = session.calibrate(calib["context"], calib["query"],
                               key=args.task)
    kvcfg = KVCommConfig(ratio=args.ratio, alpha=args.alpha)
    print(f"calibrated scores: {np.round(np.asarray(scores), 3)}")

    reqs = build_requests(tok, args.task, args.requests, args.max_new)
    t0 = time.perf_counter()
    if args.serial:
        comps, stats = serve_serial(session, reqs, kvcfg, calib_key=args.task,
                                    backend=args.decode_backend)
        mode = f"serial[{args.decode_backend}]"
    else:
        sched = Scheduler(session, kvcfg, calib_key=args.task,
                          config=SchedulerConfig(
                              capacity=args.capacity,
                              decode_backend=args.decode_backend))
        comps, stats = sched.run(reqs)
        mode = f"scheduler(cap={args.capacity}, {args.decode_backend})"
    dt = time.perf_counter() - t0

    tps = stats["tokens"] / dt
    ttft = [c.ttft_s for c in comps]
    occ = ("" if args.serial
           else f"; slot occupancy {stats['occupancy']:.2f}")
    print(f"[{mode}] served {len(comps)} requests / {stats['tokens']} "
          f"tokens in {dt:.1f}s  ({tps:.1f} tok/s; "
          f"TTFT p50 {np.median(ttft) * 1e3:.0f} ms{occ})")
    print(f"accuracy {accuracy(comps, reqs):.3f} | "
          f"transport[{args.transport}] moved "
          f"{session.transport.total_bytes / 1e6:.2f} MB over "
          f"{len(session.transport.log)} transfers")


if __name__ == "__main__":
    main()
