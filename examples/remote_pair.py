"""Remote KVComm: sender and receiver behind a real TCP socket, selected KV
crossing it through the framed remote codec.

This process plays the sender (and runs the in-process
``InMemoryTransport`` reference); the receiver is a ``KVServer`` whose
accept loop runs on a thread of the same process.  One process, because an
accelerator belongs to one process at a time: a server child would need
the chip this process already holds.  (``repro.launch.remote_serve``
runs the two halves as separate processes where each has its own device.)
The same calibrated, frozen layer selection drives both paths, so with a
lossless fp32 wire the remote predictions must be IDENTICAL to the
in-process ones — ``--self-test`` asserts exactly that, plus the
payload-bytes-vs-analytics equality, and exits non-zero on any mismatch
(the CI socket smoke test).

    PYTHONPATH=src python examples/remote_pair.py --self-test
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.comm import Agent, CommSession, InMemoryTransport
from repro.core import kv_wire_bytes
from repro.core.types import KVCommConfig
from repro.data.synthetic import SyntheticTask, TaskConfig
from repro.launch.pairs import load_pair
from repro.launch.remote_serve import KVClient, KVServer

ITEMSIZE = {"float32": 4, "float16": 2, "bfloat16": 2}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--ratio", type=float, default=0.5)
    ap.add_argument("--wire-dtype", default="float32",
                    choices=sorted(ITEMSIZE),
                    help="fp32 is lossless: remote == in-process exactly")
    ap.add_argument("--self-test", action="store_true",
                    help="assert remote == in-process and bytes == "
                         "analytics; non-zero exit on mismatch")
    args = ap.parse_args()

    cfg, tok, sender_params, receiver_params = load_pair()
    task = SyntheticTask(tok, TaskConfig("retrieval", num_facts=6, seed=42))
    batch = task.batch(args.requests)
    kvcfg = KVCommConfig(ratio=args.ratio, alpha=0.7)

    # in-process reference: calibrate once, freeze the selection, share
    # through InMemoryTransport and generate
    session = CommSession(Agent("sender", cfg, sender_params, tok),
                          Agent("receiver", cfg, receiver_params, tok),
                          InMemoryTransport())
    calib = task.batch(1)
    session.calibrate(calib["context"], calib["query"], key="retrieval6")
    select = session.selection(kvcfg, key="retrieval6")
    shared, _ = session.share(batch["context"], kvcfg, key="retrieval6")
    ref_toks = session.generate(batch["query"], shared,
                                max_new=args.max_new)
    print(f"in-process preds : {ref_toks[:, 0]}")

    # remote run: same frozen selection, KV over a real socket
    server = KVServer(Agent("receiver", cfg, receiver_params, tok))
    server.start()
    try:
        client = KVClient.connect(server.host, server.port)
        try:
            sent = client.share(session.sender, batch["context"], kvcfg,
                                select, wire_dtype=args.wire_dtype)
            remote_toks = client.generate(batch["query"],
                                          max_new=args.max_new)
        finally:
            client.close()
    finally:
        server.stop()
    print(f"remote preds     : {remote_toks[:, 0]}")

    M = int(np.asarray(select).sum())
    expect = kv_wire_bytes(cfg, args.requests, shared.prefix_len, M,
                           itemsize=ITEMSIZE[args.wire_dtype])
    print(f"payload bytes    : {sent} (analytic {expect}, "
          f"{M}/{cfg.attn_layer_count} layers, {args.wire_dtype} wire)")

    match = bool(np.array_equal(ref_toks, remote_toks))
    bytes_ok = sent == expect
    print(f"predictions match: {match}; bytes match analytics: {bytes_ok}")
    if args.self_test:
        if args.wire_dtype == "float32" and not match:
            print("SELF-TEST FAILED: lossless remote run diverged",
                  file=sys.stderr)
            return 1
        if not bytes_ok:
            print("SELF-TEST FAILED: measured bytes != analytic bytes",
                  file=sys.stderr)
            return 1
        print("SELF-TEST PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
