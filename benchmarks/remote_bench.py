"""Remote-transport overhead: what the framed codec and a real byte channel
cost on top of the in-memory hand-over.

For each transport row the SAME retrieval6 context batch is shared through
the trained pair's session at each selection ratio, with synced latency
stamps, and the per-transfer ``TransferRecord``s are averaged:

  inmemory       — device hand-over (the zero-cost floor)
  serialized     — gather + wire cast, payload materialized in-process
  remote_loop    — full framed codec through a LoopbackChannel
  remote_file    — full framed codec staged through the filesystem

Remote rows additionally report the ``serialize_s`` / ``channel_s`` /
``deserialize_s`` breakdown and the framing overhead (frame bytes vs
payload bytes — header + CRC amortized over the KV payload).

Two further sweeps:

  streaming overlap — monolithic vs chunked frames over a REAL socket to
                      a receiver SUBPROCESS (a thread would share the
                      sender's GIL and hide the pipeline), short and long
                      context: the serialize/channel/deserialize overlap
                      the kv_stream_* framing buys (pre-streaming,
                      serialize was ~86-89% of the remote wall clock).
                      The child only decodes frames and runs on the host
                      CPU (``JAX_PLATFORMS=cpu``): an accelerator belongs
                      to one process, and this one already holds it.
  wire frontier     — bytes vs prediction agreement (vs the fp32 wire) for
                      fp16 / int8 / the adaptive per-layer plan: the plan
                      must sit at int8-or-fewer bytes at matched quality.

Writes ``BENCH_remote.json`` at the repo root (CI uploads it as an
artifact); env knobs: REPRO_REMOTE_ITERS (default 8), REPRO_REMOTE_N
(batch, default 8), REPRO_REMOTE_LONG_TILE (long-context multiplier,
default 8), REPRO_REMOTE_CHUNK_KB (stream chunk size, default 64),
REPRO_REMOTE_BW_MBPS (paced-NIC bandwidth for the overlap rows,
default 200).
"""
from __future__ import annotations

import json
import os
import socket
import tempfile
import threading
import time

import numpy as np

from benchmarks import common
from repro import core
from repro.comm import (FileChannel, InMemoryTransport, RemoteTransport,
                        SerializedTransport)
from repro.core.types import KVCommConfig

ITERS = int(os.environ.get("REPRO_REMOTE_ITERS", "8"))
BATCH = int(os.environ.get("REPRO_REMOTE_N", "8"))
WIRE = os.environ.get("REPRO_REMOTE_WIRE", "float16")
LONG_TILE = int(os.environ.get("REPRO_REMOTE_LONG_TILE", "8"))
CHUNK_KB = int(os.environ.get("REPRO_REMOTE_CHUNK_KB", "64"))
BW_MBPS = float(os.environ.get("REPRO_REMOTE_BW_MBPS", "200"))
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_remote.json")


def transports():
    yield "inmemory", lambda: InMemoryTransport()
    yield "serialized", lambda: SerializedTransport(WIRE)
    yield "remote_loop", lambda: RemoteTransport(WIRE)
    yield "remote_file", lambda: RemoteTransport(
        WIRE, channel=FileChannel(tempfile.mkdtemp(prefix="kvcomm_bench_")))


def bench_transport(name: str, make, batch, ratio: float) -> dict:
    session, _, _ = common.make_session(make())
    kvcfg = KVCommConfig(ratio=ratio, selector="prior_only")
    session.share(batch["context"], kvcfg)          # warm (compiles)
    session.transport.log.clear()
    for _ in range(ITERS):
        session.share(batch["context"], kvcfg)      # synced stamps
    log = session.transport.log
    mean = lambda k: float(np.mean([getattr(r, k) for r in log]))
    row = {
        "transport": name,
        "ratio": ratio,
        "transfers": len(log),
        "payload_bytes": log[-1].n_bytes,
        "latency_ms": mean("latency_s") * 1e3,
    }
    if log[-1].frame_bytes:
        row.update({
            "frame_bytes": log[-1].frame_bytes,
            "frame_overhead": log[-1].frame_bytes / log[-1].n_bytes - 1.0,
            "serialize_ms": mean("serialize_s") * 1e3,
            "channel_ms": mean("channel_s") * 1e3,
            "deserialize_ms": mean("deserialize_s") * 1e3,
        })
    return row


def bench_paged(batch, ratio: float) -> dict:
    """The dedup-aware paged wire: same share repeated through a
    ``PageStore``-backed loopback — the repeats should hit the pool and
    ship (almost) nothing.  See ``store_bench.py`` for the full sweeps."""
    from repro.store import PageStore
    session, _, _ = common.make_session(
        RemoteTransport(WIRE, store=PageStore()))
    kvcfg = KVCommConfig(ratio=ratio, selector="prior_only")
    for _ in range(1 + ITERS):
        session.share(batch["context"], kvcfg)
    summary = session.dedup_summary()
    summary.update(transport="remote_loop_paged", ratio=ratio,
                   first_bytes=session.transport.log[0].n_bytes,
                   repeat_bytes=session.transport.log[-1].n_bytes)
    return summary


_RX_CHILD = """
import socket, sys
sys.path[:0] = {paths!r}
from repro.comm.remote import RemoteProtocolError, SocketChannel, recv_shared
s = socket.create_connection(("127.0.0.1", int(sys.argv[1])))
s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
ch = SocketChannel(s)
while True:
    try:
        shared, n = recv_shared(ch)
    except (RemoteProtocolError, OSError):
        break
    s.sendall(b"A")
"""


class _PacedWriter:
    """A fixed-bandwidth NIC model in front of a channel: ``write`` hands
    the frame off without blocking (the DMA handoff) and a drain thread
    transmits at ``bytes_per_s`` — so the sender encodes chunk i+1 while
    chunk i is on the wire, exactly the overlap a real network link
    offers and a zero-latency localhost socket hides."""

    def __init__(self, channel, bytes_per_s: float) -> None:
        import queue
        self.channel, self.bps = channel, float(bytes_per_s)
        self.q: "queue.Queue" = queue.Queue()
        self.t = threading.Thread(target=self._drain, daemon=True)
        self.t.start()

    def _drain(self) -> None:
        # token bucket, not a per-frame sleep: the kernel rounds sleeps
        # up to ~1 ms, so pacing 64 KB frames one sleep at a time would
        # model a far slower NIC than asked for.  Short debts accumulate
        # until one >2 ms sleep pays them off; the average rate is bps.
        due = None
        while True:
            data = self.q.get()
            if data is None:
                return
            now = time.perf_counter()
            due = max(due if due is not None else now, now)
            due += len(data) / self.bps
            if due - now > 0.002:
                time.sleep(due - now)
            self.channel.write(data)

    def write(self, data) -> None:
        self.q.put(bytes(data))

    def join(self) -> None:
        self.q.put(None)
        self.t.join()


def bench_streaming_overlap(session, cfg, batch) -> list:
    """Monolithic vs streamed frames against a receiver in its OWN
    process (the deployment the remote transport exists for — a threaded
    receiver would share the sender's GIL and serialize the very work the
    chunked frames pipeline).  Wall clock runs send-start to the
    receiver's decoded-ack: streamed chunks let the receiver decode chunk
    i while the sender encodes and writes chunk i+1, so the wall drops
    below the serial serialize + channel + deserialize sum.  Raw rows use
    the localhost socket as-is (channel time ~0 — streaming can only
    match, not beat, the monolithic frame); paced rows put the
    ``_PacedWriter`` NIC model at ``REPRO_REMOTE_BW_MBPS`` in front of
    it, where the serialize/channel/deserialize overlap is the win."""
    import subprocess
    import sys
    from repro.comm.remote import (SocketChannel, encode_kv_transfer,
                                   send_shared)
    kvcfg = KVCommConfig(ratio=0.5, selector="prior_only")
    select = core.make_selection(cfg, kvcfg)
    ctx = np.asarray(batch["context"])
    repo = os.path.join(os.path.dirname(__file__), "..")
    paths = [os.path.abspath(repo), os.path.abspath(
        os.path.join(repo, "src"))]
    # the paced writer thread must grab the GIL promptly when its sleep
    # expires; the default 5 ms switch interval adds up to one interval
    # of wake latency per pacer sleep, dwarfing the 64 KB frame times
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    srv.settimeout(120.0)
    child = subprocess.Popen(
        [sys.executable, "-c", _RX_CHILD.format(paths=paths),
         str(srv.getsockname()[1])],
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        conn, _ = srv.accept()
    except socket.timeout:
        child.kill()
        raise RuntimeError(f"receiver child never connected (exit code "
                           f"{child.wait()})") from None
    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    ch = SocketChannel(conn)
    rows = []
    try:
        for label, context in (("short", ctx),
                               ("long", np.concatenate([ctx] * LONG_TILE,
                                                       axis=1))):
            kv, _, _ = session.sender.export_kv(context)

            def run(chunk_bytes, paced=False):
                writer = (_PacedWriter(ch, BW_MBPS * 1e6) if paced
                          else ch)
                t0 = time.perf_counter()
                n = send_shared(writer, kvcfg, kv, select,
                                wire_dtype=WIRE, chunk_bytes=chunk_bytes)
                conn.recv(1)                   # receiver decoded + acked
                wall = time.perf_counter() - t0
                if paced:
                    writer.join()
                return wall, n

            run(None), run(CHUNK_KB * 1024)    # warm both encode paths
            # encode-only cost (the serialize share of the mono wall)
            t0 = time.perf_counter()
            encode_kv_transfer(kvcfg, kv, select, wire_dtype=WIRE)
            ser = time.perf_counter() - t0
            for paced in (False, True):
                mono = min(run(None, paced)[0] for _ in range(ITERS))
                stream, n_bytes = None, None
                for _ in range(ITERS):
                    w, n_bytes = run(CHUNK_KB * 1024, paced)
                    stream = w if stream is None else min(stream, w)
                row = {
                    "transport": ("remote_socket_overlap_paced" if paced
                                  else "remote_socket_overlap"),
                    "context": label,
                    "context_len": int(context.shape[1]),
                    "payload_bytes": int(n_bytes),
                    "chunk_bytes": CHUNK_KB * 1024,
                    "serialize_ms": ser * 1e3,
                    "mono_wall_ms": mono * 1e3,
                    "stream_wall_ms": stream * 1e3,
                    "serialize_pct_of_mono_wall": ser / mono,
                    "serialize_pct_of_stream_wall": ser / stream,
                    "overlap_speedup": mono / stream,
                }
                if paced:
                    row["bandwidth_mbps"] = BW_MBPS
                rows.append(row)
                tag = f"paced {BW_MBPS:g} MB/s" if paced else "raw"
                print(f"overlap[{label}, {tag}] ctx "
                      f"{row['context_len']}: mono "
                      f"{row['mono_wall_ms']:.2f} ms (serialize "
                      f"{row['serialize_pct_of_mono_wall'] * 100:.0f}%) "
                      f"-> streamed {row['stream_wall_ms']:.2f} ms "
                      f"({row['overlap_speedup']:.2f}x)")
    finally:
        sys.setswitchinterval(switch)
        ch.close()
        srv.close()
        child.wait(timeout=30)
    return rows


def bench_wire_frontier(batch) -> list:
    """The bytes-vs-quality frontier: each wire's measured bytes and its
    prediction agreement against the fp32 wire on the same batch.  The
    adaptive plan (``CommSession.wire_plan`` off the frozen selection's
    prior) must cost int8-or-fewer bytes."""
    kvcfg = KVCommConfig(ratio=0.5, selector="prior_only")
    plan = common.make_session()[0].wire_plan(kvcfg)
    wires = [("float32", "float32"), ("float16", "float16"),
             ("int8", "int8"), ("adaptive", plan.spec)]
    preds, rows = {}, []
    for label, wd in wires:
        session, _, _ = common.make_session(SerializedTransport(wd))
        shared, _ = session.share(batch["context"], kvcfg)
        out = session.receiver.prefill(batch["query"], shared, max_new=0)
        preds[label] = np.argmax(np.asarray(out.logits[:, -1, :]), axis=-1)
        rows.append({"transport": "wire_frontier", "wire": label,
                     "wire_dtype": wd,
                     "payload_bytes": session.transport.total_bytes})
    by = {r["wire"]: r for r in rows}
    for r in rows:
        r["pred_agreement"] = float(np.mean(preds[r["wire"]]
                                            == preds["float32"]))
        r["bytes_vs_fp32"] = (r["payload_bytes"]
                              / by["float32"]["payload_bytes"])
        print(f"frontier {r['wire']:<9} {r['payload_bytes']:>8} B "
              f"({r['bytes_vs_fp32']:.3f}x fp32), agreement "
              f"{r['pred_agreement']:.3f}")
    by["adaptive"]["plan"] = plan.spec
    by["adaptive"]["bytes_vs_int8"] = (by["adaptive"]["payload_bytes"]
                                       / by["int8"]["payload_bytes"])
    return rows


def main() -> None:
    _, _, tok = common.make_session()
    batch = common.eval_batch(tok, "countries", BATCH)
    rows = []
    for ratio in (0.3, 0.5):
        base = None
        for name, make in transports():
            row = bench_transport(name, make, batch, ratio)
            if name == "inmemory":
                base = row["latency_ms"]
            row["vs_inmemory"] = row["latency_ms"] / max(base, 1e-9)
            rows.append(row)
            extra = ("" if "serialize_ms" not in row else
                     f"  [ser {row['serialize_ms']:.2f} + chan "
                     f"{row['channel_ms']:.2f} + deser "
                     f"{row['deserialize_ms']:.2f} ms; frame +"
                     f"{row['frame_overhead'] * 100:.2f}%]")
            print(f"ratio {ratio}: {name:<12} {row['latency_ms']:7.2f} ms "
                  f"({row['payload_bytes']} B, "
                  f"{row['vs_inmemory']:.2f}x in-memory){extra}")
        paged = bench_paged(batch, ratio)
        rows.append(paged)
        print(f"ratio {ratio}: {'remote_paged':<12} dedup hit rate "
              f"{paged['hit_rate']:.2f} over {paged['transfers']} transfers "
              f"({paged['first_bytes']} B cold, "
              f"{paged['repeat_bytes']} B repeat)")
    session, cfg, _ = common.make_session()
    rows += bench_streaming_overlap(session, cfg, batch)
    rows += bench_wire_frontier(batch)
    out = {"wire_dtype": WIRE, "iters": ITERS, "batch": BATCH, "rows": rows}
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {os.path.abspath(OUT_PATH)}")


if __name__ == "__main__":
    main()
