"""Smoke test of the served KVComm path on one TPU chip.

    python chip_smoke.py              # one chip: phases 1-4 below
    python chip_smoke.py --chips 4    # four chips: the replica fabric only
    python chip_smoke.py --rehearse   # CPU rehearsal at a tiny width

It drives the path a user calls — ``repro.launch.pairs.load_pair(arch=...)``,
``CommSession`` + ``Scheduler`` as ``repro.launch.serve`` runs them, and a
``KVServer``/``KVClient`` pair over 127.0.0.1 — at the full width of
``llama3.2-3b-pair`` (28 layers, d=3072, 24/8 heads, vocab 128256, bf16)
with weights drawn from seed 0.  Everything runs in this one process: an
accelerator belongs to one process at a time.

Phases (any failure exits non-zero):

1. device check — ``jax.devices()[0].platform`` must be ``tpu``; there is no
   CPU fallback.
2. kernel check — the compiled ``ragged_decode`` kernel, prefix-free and
   two-segment, against ``kernels.ref.ragged_decode_reference``.
3. in-process scheduler — calibrate, then 8 requests with contexts tiled to
   512-1,024 tokens, ``max_new`` 8, under the ``reference`` and the
   ``pallas`` decode backend: finite logits, first-step logits of the two
   backends within a stated bound, and a ``tpu_custom_call`` in the compiled
   pallas decode step (no silent interpreter or masked-dense fallback).
4. socket served path — a ``KVServer`` thread: a streamed float32 share whose
   tokens equal an in-process ``InMemoryTransport`` run, then a paged int8
   share whose pages and bytes equal ``kv_wire_bytes_paged``.

``--chips 4`` runs only the fabric: four ``KVServer`` replicas, each
receiver's parameters on its own device, behind a round-robin ``Router``,
against the same requests through one replica.

Lines before the last are smoke output (phase wall times, compile counts,
peak memory), not metrics.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "llama3.2-3b-pair"
SEED = 0


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok, what: str) -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


class CompileWatch:
    """Counts backend compiles and persistent-cache hits/misses through
    ``jax.monitoring`` so a second run can show what the cache saved."""

    def __init__(self) -> None:
        self.events = {"cache_hits": 0, "cache_misses": 0}
        self.compiles, self.compile_s = 0, 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_) -> None:
        key = name.rsplit("/", 1)[-1]
        if key in self.events:
            self.events[key] += 1

    def _duration(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def report(self, cache_dir: str) -> None:
        log(f"compiles: {self.compiles} programs, {self.compile_s:.1f} s "
            f"backend compile; persistent cache {cache_dir}: "
            f"{self.events['cache_hits']} hits, "
            f"{self.events['cache_misses']} misses")


def peak_memory() -> str:
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out.append(f"{d.id}:{stats['peak_bytes_in_use'] / 2**30:.2f}GiB")
    return " ".join(out) or "not reported by this backend"


def shapes_of(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


# ---------------------------------------------------------------------------
# phase 2: kernel
# ---------------------------------------------------------------------------
def kernel_phase(cfg, *, batch: int, seq: int, prefix_len: int) -> None:
    from repro.kernels import ref
    from repro.kernels.ragged_decode import ragged_decode
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (batch, Hq, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (batch, seq, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (batch, seq, Hkv, D), jnp.bfloat16)
    for pfx_len in (0, prefix_len):
        kv_len = jax.random.randint(ks[3], (batch,), pfx_len + 1, seq + 1)
        pfx = jax.random.randint(ks[4], (batch,), 0, pfx_len + 1)
        kv_len, pfx = kv_len.at[-1].set(0), pfx.at[-1].set(0)  # a dead row
        out = jax.jit(lambda *a: ragged_decode(*a, prefix_len=pfx_len))(
            q, k, v, kv_len, pfx)
        # the oracle in float32 on the same bf16 inputs: the kernel also
        # accumulates in float32, so what differs is its bf16 output
        # rounding (2^-8 relative) and p.v at Mosaic's matmul precision
        # (p rounded to bf16); 2e-2 rel / 2e-3 abs leaves ~5 bf16 ulps
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        with jax.default_matmul_precision("highest"):
            want = ref.ragged_decode_reference(
                *f32, kv_len=kv_len, prefix_lens=pfx, prefix_len=pfx_len)
        got = np.asarray(out.astype(jnp.float32))
        want = np.asarray(want)
        err = float(np.max(np.abs(got - want)))
        log(f"kernel ragged_decode prefix_len={pfx_len}: max |err| {err:.2e}"
            f" (max |ref| {float(np.max(np.abs(want))):.3f})")
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)
        check(np.all(got[-1] == 0.0), "dead row must return zeros")


# ---------------------------------------------------------------------------
# phase 3: the scheduler, both decode backends
# ---------------------------------------------------------------------------
def scheduler_phase(session, tok, *, tile: int, on_tpu: bool) -> None:
    from repro.core import protocol
    from repro.core.types import KVCommConfig
    from repro.launch.serve import build_requests
    from repro.serving.scheduler import Scheduler, SchedulerConfig
    kvcfg = KVCommConfig(ratio=0.5, alpha=0.7)
    reqs = build_requests(tok, "retrieval", 8, 8, context_tile=tile)
    log(f"scheduler: 8 requests, contexts {min(len(r.context) for r in reqs)}"
        f"-{max(len(r.context) for r in reqs)} tokens, max_new 8")
    receiver = session.receiver
    step = receiver.ragged_step
    first = {}

    def recording_step(tokens, cache, shared, prefix_lens, active,
                       backend="reference"):
        # keep the FIRST iteration of each backend: both start from the
        # same admitted table, so their logits are directly comparable
        args = (tokens, cache, shared, prefix_lens, active)
        if backend not in first:
            first[backend] = {"args": shapes_of(args)}
        out = step(*args, backend=backend)
        first[backend].setdefault("logits", out[1])
        return out

    receiver.ragged_step = recording_step
    comps = {}
    try:
        for backend in ("reference", "pallas"):
            t0 = time.perf_counter()
            sched = Scheduler(session, kvcfg, calib_key="retrieval",
                              config=SchedulerConfig(
                                  capacity=8, decode_backend=backend))
            comps[backend], stats = sched.run(reqs)
            log(f"scheduler[{backend}]: {stats['tokens']} tokens in "
                f"{stats['iterations']} iterations, "
                f"{time.perf_counter() - t0:.1f} s wall (compiles included)")
    finally:
        receiver.ragged_step = step
    for backend in comps:
        check(len(comps[backend]) == 8
              and all(len(c.tokens) == 8 for c in comps[backend]),
              f"{backend}: 8 completions of 8 tokens")
        check(np.all(np.isfinite(np.asarray(first[backend]["logits"]))),
              f"{backend}: finite first-step logits")
    # the first token comes from the backend-independent prefill
    check([c.pred for c in comps["reference"]]
          == [c.pred for c in comps["pallas"]], "prefill tokens differ")
    a = np.asarray(first["reference"]["logits"])
    b = np.asarray(first["pallas"]["logits"])
    rel = float(np.max(np.abs(a - b)) / max(np.max(np.abs(a)), 1e-9))
    same = float(np.mean(a.argmax(-1) == b.argmax(-1)))
    log(f"first-step logits pallas vs reference: max rel {rel:.2e}, "
        f"argmax agreement {same:.3f} (not required: random-weight "
        "near-ties)")
    # the backends differ only in decode-attention arithmetic (masked-dense
    # bf16 einsums vs the kernel's float32 online softmax); compounded over
    # the layers that stays a few bf16 ulps of the peak logit, while a wrong
    # mask (attending pad or losing the prefix) moves logits by O(1)
    check(rel < 5e-2, f"decode backends disagree: {rel:.3f} rel")
    if on_tpu:
        hlo = protocol._ragged_decode_step_jit.lower(
            receiver.params, receiver.cfg, *first["pallas"]["args"],
            backend="pallas").compile().as_text()
        check("tpu_custom_call" in hlo,
              "the pallas decode step compiled without the Mosaic kernel")
        log("pallas decode step contains tpu_custom_call")


# ---------------------------------------------------------------------------
# phase 4: the socket served path
# ---------------------------------------------------------------------------
def socket_phase(session, tok, *, tile: int) -> None:
    from repro.comm import InMemoryTransport
    from repro.core import kv_wire_bytes, kv_wire_bytes_paged
    from repro.core.types import KVCommConfig
    from repro.data.synthetic import SyntheticTask, TaskConfig
    from repro.launch.remote_serve import KVClient, KVServer
    from repro.store import PageStore
    cfg = session.cfg
    kvcfg = KVCommConfig(ratio=0.5, alpha=0.7)
    select = session.selection(kvcfg, key="retrieval")
    M = int(np.asarray(select).sum())
    task = SyntheticTask(tok, TaskConfig("retrieval", num_facts=6, seed=7))
    batch = task.batch(8)
    ctx = np.tile(batch["context"], (1, tile))
    query, B, max_new = batch["query"], ctx.shape[0], 4
    check(isinstance(session.transport, InMemoryTransport),
          "the reference session must hand KV over in memory")
    shared, _ = session.share(ctx, kvcfg, key="retrieval")
    want = session.generate(query, shared, max_new=max_new)
    Sc, page_len = shared.prefix_len, 16

    server = KVServer(session.receiver,
                      store=PageStore(page_len=page_len,
                                      capacity_bytes=256 << 20))
    server.start()
    try:
        client = KVClient.connect(server.host, server.port)
        try:
            n = client.share(session.sender, ctx, kvcfg, select,
                             wire_dtype="float32", chunk_bytes=1 << 20)
            got = client.generate(query, max_new=max_new)
            n_pg, total, sent = client.share_paged(
                session.sender, ctx, kvcfg, select, page_len=page_len,
                wire_dtype="int8")
            paged_toks = client.generate(query, max_new=max_new)
            _, _, resent = client.share_paged(
                session.sender, ctx, kvcfg, select, page_len=page_len,
                wire_dtype="int8")
        finally:
            client.close()
    finally:
        server.stop()
    log(f"socket fp32 stream: {n} B for {M} layers x {B} x {Sc} tokens; "
        f"tokens equal in-process: {np.array_equal(got, want)}")
    # bf16 -> float32 wire -> bf16 is exact, so remote == in-process
    np.testing.assert_array_equal(got, want)
    check(n == kv_wire_bytes(cfg, B, Sc, M, itemsize=4),
          f"fp32 stream shipped {n} B")
    pages = M * -(-Sc // page_len)
    log(f"socket paged int8: {sent}/{total} pages, {n_pg} B; repeat share "
        f"shipped {resent} pages")
    check((total, sent, resent) == (pages, pages, 0),
          f"pages {(total, sent, resent)} != {(pages, pages, 0)}")
    # int8 ships one float32 scale per selected layer for k and for v
    check(n_pg == kv_wire_bytes_paged(cfg, B, Sc, M, page_len=page_len,
                                      pages_sent=sent, itemsize=1)
          + 2 * M * 4, f"paged int8 shipped {n_pg} B")
    check(paged_toks.shape == (B, max_new)
          and np.all((paged_toks >= 0) & (paged_toks < cfg.vocab_size)),
          "paged answer is not (B, max_new) token ids")


# ---------------------------------------------------------------------------
# --chips 4: the replica fabric
# ---------------------------------------------------------------------------
def fabric_phase(cfg, tok, params, *, tile: int) -> None:
    from repro.comm import Agent
    from repro.core.types import KVCommConfig
    from repro.data.synthetic import SyntheticTask, TaskConfig
    from repro.launch.remote_serve import KVServer
    from repro.serving.fabric import Replica, ReplicaSet, Router, RouterConfig
    from repro.serving.scheduler import Request
    from repro.store import PageStore
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, has {devices}")
    kvcfg = KVCommConfig(ratio=0.5, selector="prior_only")
    task = SyntheticTask(tok, TaskConfig("retrieval", num_facts=6, seed=11))
    batch = task.batch(8)
    reqs = [Request(rid=i, context=np.tile(batch["context"][i], tile),
                    query=np.asarray(batch["query"][i], np.int32),
                    max_new=4) for i in range(8)]
    placed = [params] + [jax.device_put(params, d) for d in devices[1:]]
    servers = []
    for i, p in enumerate(placed):
        homes = {d for leaf in jax.tree.leaves(p) for d in leaf.devices()}
        check(homes == {devices[i]}, f"replica r{i} params on {homes}")
        servers.append(KVServer(Agent(f"recv-{i}", cfg, p, tok),
                                store=PageStore(page_len=16)))
    for s in servers:
        s.start()
    sender = Agent("sender", cfg, params, tok)
    rcfg = RouterConfig(wire_dtype="float32", page_len=16,
                        policy="round_robin")

    def fleet(ids):
        # a replica's first query compiles its generate program, which at
        # full width outlasts the default 10 s read timeout
        return ReplicaSet([Replica(f"r{i}", servers[i].host, servers[i].port,
                                   io_timeout_s=900.0) for i in ids])

    try:
        t0 = time.perf_counter()
        router = Router(sender, kvcfg, fleet(range(4)), config=rcfg)
        try:
            comps, metrics = router.run(reqs)
        finally:
            router.close()
        log(f"fabric: 8 requests over 4 replicas in "
            f"{time.perf_counter() - t0:.1f} s wall, served "
            f"{metrics['served']}")
        single = Router(sender, kvcfg, fleet([0]), config=rcfg)
        try:
            ref, _ = single.run(reqs)
        finally:
            single.close()
    finally:
        for s in servers:
            s.stop()
    check(metrics["served"] == {f"r{i}": 2 for i in range(4)},
          f"round robin served {metrics['served']}")
    parity = all(np.array_equal(c.tokens, r.tokens)
                 for c, r in zip(comps, ref))
    log(f"fabric: token parity 4 replicas vs one replica: {parity}")
    check(parity, "replicas on different devices disagree")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-replica fabric phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: a 2-layer cut of the config, "
                         "kernels interpreted, short contexts; prints no "
                         "device result")
    args = ap.parse_args(argv)

    # phase 1: the device, before anything else is imported or built
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{platform!r}); nothing was run", file=sys.stderr)
        return 2
    log(f"devices: {len(devices)} x {devices[0].device_kind} ({platform})")

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.comm import Agent, CommSession, InMemoryTransport
    from repro.configs.registry import get_config
    from repro.data.synthetic import SyntheticTask, TaskConfig
    from repro.launch.pairs import load_pair
    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    watch = CompileWatch()

    arch = ARCH
    if args.rehearse:
        import dataclasses
        arch = dataclasses.replace(get_config(ARCH).reduced(),
                                   name=ARCH + "-rehearsal")
    t0 = time.perf_counter()
    cfg, tok, params, _ = load_pair(arch, seed=SEED)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{cfg.name}: {cfg.num_layers} layers, d={cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {cfg.dtype}, {n_params / 1e9:.2f} B "
        f"params from seed {SEED} in "
        f"{time.perf_counter() - t0:.1f} s")
    tile, kernel_seq, kernel_prefix = (4, 40, 16) if args.rehearse \
        else (64, 2112, 1040)

    def phase(name, fn, *a, **kw):
        t = time.perf_counter()
        fn(*a, **kw)
        log(f"phase {name}: passed in {time.perf_counter() - t:.1f} s; "
            f"peak memory {peak_memory()}")

    if args.chips == 4:
        phase("fabric", fabric_phase, cfg, tok, params,
              tile=2 if args.rehearse else 64)
    else:
        phase("kernel", kernel_phase, cfg, batch=8, seq=kernel_seq,
              prefix_len=kernel_prefix)
        session = CommSession(Agent("sender", cfg, params, tok),
                              Agent("receiver", cfg, params, tok),
                              InMemoryTransport())
        calib = SyntheticTask(tok, TaskConfig("retrieval", num_facts=6,
                                              seed=42)).batch(1)
        session.calibrate(calib["context"], calib["query"], key="retrieval")
        phase("scheduler", scheduler_phase, session, tok, tile=tile,
              on_tpu=platform == "tpu")
        phase("socket", socket_phase, session, tok, tile=8)
    watch.report(cache_dir)
    if args.rehearse:
        log("rehearsal passed (CPU, interpreted kernels): not a device "
            "result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
