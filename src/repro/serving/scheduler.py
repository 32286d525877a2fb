"""Overlapped continuous-batching scheduler over a ``CommSession``.

The serving subsystem the paper's deployment implies (§5 "scalable and
efficient multi-agent systems"), in the Orca/vLLM iteration-level lineage
(continuous batching per the vllm-production-stack papers in PAPERS.md):

  * **Slot table** — a fixed-capacity batched serving cache whose rows hold
    in-flight requests at *different* generation offsets.  One donated
    compiled ragged step per iteration (``core.ragged_decode_step``)
    advances every live row by a token, masking per-row ``kv_len`` exactly
    like ``kernels.flash_decode``'s per-batch int32 ``kv_len`` does on the
    accelerator path.  Finished slots are refilled mid-flight — the batch
    never drains to admit work.

  * **Bucket padding** — request prefixes (``Sc``) and queries are padded
    up to configured buckets, so one frozen selection compiles a small
    fixed set of shapes: ONE ragged step per (selection bitmask, table
    geometry) plus one prefill/insert pair per (prefix bucket, query
    bucket) — never a shape per request.  Pad positions are masked out of
    attention by per-row real lengths (``prefix_lens`` + per-row ``len``),
    so a bucketed request answers exactly like an unpadded one.

  * **Overlap** — every stage is async-dispatched: admission (sender
    export -> transport ``send(sync=False)`` with a deferred latency stamp
    -> bucketed receiver prefill -> donated slot insert) enqueues behind
    the in-flight decode step without a single host sync.  The host reads
    results one iteration behind (double buffering), so sender-side work
    for request N+1 executes while the table decodes.

``serve_serial`` is the blocking reference implementation (per-request
share -> prefill -> per-token stream) that the scheduler must match
token-for-token; ``benchmarks/serve_bench.py`` races the two.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.comm.resilience import DegradationEvent
from repro.comm.session import CommSession, _LADDER_ERRORS
from repro.core.channel import TransferRecord
from repro.core.types import KVCommConfig, SharedKV
from repro.models import transformer as tfm
from repro.utils import spans


# ---------------------------------------------------------------------------
# requests and results
# ---------------------------------------------------------------------------
@dataclass
class Request:
    """One serving request: a sender-side context, a receiver-side query,
    and a per-request generation budget (mixed lengths are the point)."""
    rid: int
    context: np.ndarray          # (Sc,) int32 — sender context tokens
    query: np.ndarray            # (Sq,) int32 — receiver query tokens
    max_new: int = 8             # total tokens (first comes from prefill)
    answer: Optional[int] = None


@dataclass
class Completion:
    rid: int
    tokens: np.ndarray           # (max_new,) generated token ids
    ttft_s: float = 0.0          # submit -> first token materialized
    queue_s: float = 0.0         # submit -> its admission starts
    admitted_s: float = 0.0      # submit -> its admission is dispatched
    # non-None when the request's KV transfer degraded (fallback transport
    # or text-only baseline) instead of riding the primary path
    degradation: Optional[DegradationEvent] = None
    @property
    def pred(self) -> int:
        return int(self.tokens[0])


@dataclass
class SchedulerConfig:
    capacity: int = 8            # slot-table rows (max in-flight requests)
    prefix_bucket: int = 16      # Sc rounds up to a multiple of this
    query_bucket: int = 8        # Sq rounds up to a multiple of this
    eos_token: Optional[int] = None
    # EOS-based early exit: when set, a slot that emits this token is
    # retired (and its row readmitted) instead of decoding to max_new.
    # Detection rides the existing one-iteration-behind host reads, so a
    # finishing request wastes at most two masked slot iterations — never
    # a host sync.  Completions are truncated at the EOS inclusive, which
    # keeps token-for-token parity with ``serve_serial(eos_token=...)``.
    decode_backend: str = "reference"
    # attention impl of the per-iteration ragged step: "reference" keeps
    # the masked-dense parity oracle, "pallas" runs the fused two-segment
    # kernel (kernels.ragged_decode).  Admission prefill/insert are
    # backend-independent, so switching adds exactly one compiled step
    # per (selection, table geometry).


def _bucket(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


@dataclass
class _Slot:
    req: Request
    start_hist: int              # history row holding its first decode tok
    col: int = -1                # slot-table column the request occupied
    decoded: int = 0


# ---------------------------------------------------------------------------
# jitted admission insert (donated table; compiles per bucket pair)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("src_prefix", "dst_prefix",
                                    "row_max_len"),
                   donate_argnums=(0,))
def _insert_jit(table, row, slot, new_len, src_prefix, dst_prefix,
                row_max_len):
    from repro.core.protocol import TRACE_COUNTS
    TRACE_COUNTS["scheduler_insert"] += 1
    table = tfm.cache_insert_row(table, row, slot, src_prefix=src_prefix,
                                 dst_prefix=dst_prefix,
                                 row_max_len=row_max_len)
    table["len"] = table["len"].at[slot].set(new_len)
    return table


@functools.partial(jax.jit,
                   static_argnames=("cfg", "layers", "src_prefix",
                                    "dst_prefix", "row_max_len"),
                   donate_argnums=(0,))
def _insert_paged_jit(table, row, slot, new_len, prefix, *, cfg, layers,
                      src_prefix, dst_prefix, row_max_len):
    """The page-table-consuming admission insert: the prefix region comes
    from a ``PageStore.gather_prefix`` rebuild instead of the request
    row's own buffers.  Compiles per (selection, prefix bucket, query
    bucket) — the page-count bucket IS the prefix bucket (pages are
    fixed-size), so attaching a store adds no new compile axis."""
    from repro.core.protocol import TRACE_COUNTS
    TRACE_COUNTS["scheduler_insert_paged"] += 1
    table = tfm.cache_insert_row_paged(cfg, table, row, slot, prefix,
                                       layers=layers,
                                       src_prefix=src_prefix,
                                       dst_prefix=dst_prefix,
                                       row_max_len=row_max_len)
    table["len"] = table["len"].at[slot].set(new_len)
    return table


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------
class Scheduler:
    """Iteration-level request scheduler on one sender/receiver session.

    All requests of one scheduler share the session's frozen selection
    (``calib_key``): the slot table's partitioned cache geometry is
    selection-static, which is what makes the ragged step a single compile.
    """

    def __init__(self, session: CommSession, kvcfg: KVCommConfig, *,
                 calib_key: Optional[str] = None,
                 config: Optional[SchedulerConfig] = None):
        assert not session.is_hetero, \
            "the scheduler serves homogeneous pairs (hetero: ROADMAP)"
        cfg = session.cfg
        for spec in cfg.layer_plan():
            assert spec.kind in ("attn", "shared_attn"), \
                "continuous batching covers attention-only models for now " \
                "(ragged SSM rows would need per-row state rewind)"
            assert not spec.cross_attn, "cross-attention rows not supported"
        assert cfg.arch_type != "audio", "ragged rows need a rope arch"
        self.session = session
        self.kvcfg = kvcfg
        self.calib_key = calib_key
        self.config = config or SchedulerConfig()
        self.select = session.selection(kvcfg, key=calib_key)
        self.layers = core.selected_layer_ids(self.select)
        self.packed = session.transport.packed

    # -- table construction -------------------------------------------------
    def _zero_shared(self, prefix_len: int, capacity: int) -> SharedKV:
        cfg = self.session.cfg
        Hkv, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)
        if self.packed:
            M = len(self.layers)
            payload = {p: jnp.zeros((M, capacity, prefix_len, Hkv, Dh), dt)
                       for p in ("k", "v")}
            return core.build_packed(self.kvcfg, payload, self.layers,
                                     prefix_len, select=self.select)
        L = cfg.attn_layer_count
        kv = {p: jnp.zeros((L, capacity, prefix_len, Hkv, Dh), dt)
              for p in ("k", "v")}
        return core.build_shared(self.kvcfg, kv, self.select)

    # -- admission ----------------------------------------------------------
    def _admit(self, req: Request, state: dict, slot: int,
               force_baseline: bool = False):
        """Enqueue the whole admission pipeline for one request — sender
        prefill, transport transfer (deferred stamp), bucketed receiver
        prefill, donated slot insert — without any host sync.

        ``force_baseline`` skips the share entirely and admits the request
        text-only (the quarantine path ``run`` takes when a share raised
        through the session's ladder — or there is no ladder)."""
        sess, cfgd = self.session, self.config
        degraded: Optional[DegradationEvent] = None
        if force_baseline:
            shared = None
        else:
            shared, _ = sess.share(req.context[None, :], self.kvcfg,
                                   key=self.calib_key, sync=False,
                                   rid=req.rid)
            degraded = sess.last_degradation
        if shared is None:
            # baseline admission: a zero prefix that per-row prefix_lens=0
            # masks out entirely (and zeroes the pos shift), so the row
            # answers exactly like prefill(query, None) — through the SAME
            # compiled prefill/insert the healthy path uses (the bucket
            # matches what this request's real share would have used)
            scb = min(_bucket(int(req.context.shape[0]) + 1,
                              cfgd.prefix_bucket), state["dst_prefix"])
            shared = self._zero_shared(scb, 1)
            sc_real = 0
        else:
            if self.packed:
                assert shared.layers == self.layers, \
                    "a scheduler serves ONE frozen selection; calibrate " \
                    "per task and run one scheduler per calib_key"
            sc_real = shared.prefix_len
            scb = min(_bucket(sc_real, cfgd.prefix_bucket),
                      state["dst_prefix"])
        sq_real = int(req.query.shape[0])
        sqb = min(_bucket(sq_real, cfgd.query_bucket), state["query_max"])
        qry = np.full((1, sqb), self.pad_token, np.int32)
        qry[0, :sq_real] = req.query
        with spans.span(spans.ADMIT_PREFILL, rid=req.rid):
            out = sess.receiver.prefill(
                qry, core.pad_prefix(shared, scb),
                max_new=state["budget"],
                prefix_lens=jnp.full((1,), sc_real, jnp.int32))
        tok1 = jnp.argmax(out.logits[:, sq_real - 1, :], axis=-1)  # (1,)
        if req.max_new > 1:
            with spans.span(spans.ADMIT_INSERT, rid=req.rid):
                store = getattr(sess.transport, "store", None)
                btab = getattr(sess.transport, "last_table", None)
                # a degraded/baseline admission must NOT consume the
                # store's last_table — it belongs to a previous request's
                # (healthy) exchange, the wrong prefix for this row
                if self.packed and store is not None and btab is not None \
                        and degraded is None and not force_baseline:
                    # paged admission: rebuild the prefix from the
                    # store's content-addressed pages (bit-identical to the
                    # padded prefix the row was prefilled with) and let the
                    # donated insert consume the page gather.  Must happen
                    # before the NEXT request's share() swaps/releases the
                    # pinned table.
                    prefix_pages = store.gather_prefix(btab, scb)
                    state["table"] = _insert_paged_jit(
                        state["table"], out.cache, slot,
                        state["dst_prefix"] + sq_real, prefix_pages,
                        cfg=sess.cfg, layers=self.layers,
                        src_prefix=scb, dst_prefix=state["dst_prefix"],
                        row_max_len=sqb + state["budget"])
                else:
                    state["table"] = _insert_jit(
                        state["table"], out.cache, slot,
                        state["dst_prefix"] + sq_real,
                        src_prefix=scb, dst_prefix=state["dst_prefix"],
                        row_max_len=sqb + state["budget"])
                state["prefix_lens"] = \
                    state["prefix_lens"].at[slot].set(sc_real)
                state["cur_tok"] = state["cur_tok"].at[slot, 0].set(tok1[0])
                state["active"] = state["active"].at[slot].set(True)
        return tok1

    @property
    def pad_token(self) -> int:
        return int(self.session.receiver.tok.PAD)

    # -- the loop -----------------------------------------------------------
    def run(self, requests: Sequence[Request]
            ) -> Tuple[List[Completion], Dict[str, float]]:
        """Serve a request stream to completion. Returns the completions
        (rid order) and scheduler metrics (iterations, mean slot occupancy,
        generated-token count).

        Every host phase is a ``kvcomm.sched.*`` span (``utils.spans``)."""
        if not requests:
            return [], {"iterations": 0, "occupancy": 0.0, "tokens": 0}
        sess, cfgd = self.session, self.config
        n_deg0 = len(sess.degradations)   # events from THIS run only
        cap = cfgd.capacity
        budget = max(r.max_new for r in requests) - 1
        dst_prefix = _bucket(max(int(r.context.shape[0]) + 1
                                 for r in requests), cfgd.prefix_bucket)
        query_max = _bucket(max(int(r.query.shape[0]) for r in requests),
                            cfgd.query_bucket)
        with spans.span(spans.SCHED_SETUP):
            zshared = self._zero_shared(dst_prefix, cap)
            table = tfm.init_cache(sess.cfg, cap, query_max + max(budget, 1),
                                   shared=zshared)
            table["len"] = jnp.full((cap,), dst_prefix, jnp.int32)
            self.meta = zshared.meta()
            state = {
                "table": table,
                "prefix_lens": jnp.full((cap,), dst_prefix, jnp.int32),
                "cur_tok": jnp.zeros((cap, 1), jnp.int32),
                "active": jnp.zeros((cap,), bool),
                "dst_prefix": dst_prefix,
                "query_max": query_max,
                "budget": max(budget, 1),
            }

        eos = cfgd.eos_token

        def _retire(i: int) -> None:
            done[slots[i].req.rid] = slots[i]
            slots[i] = None
            state["active"] = state["active"].at[i].set(False)

        pending = deque(sorted(requests, key=lambda r: r.rid))
        slots: List[Optional[_Slot]] = [None] * cap
        first_tok: Dict[int, jnp.ndarray] = {}
        done: Dict[int, _Slot] = {}
        ttft: Dict[int, float] = {}
        queued: Dict[int, Tuple[float, float]] = {}   # (queue_s, admitted_s)
        fetch_q: deque = deque()      # (iteration_enqueued, array, rids)
        history: List[jnp.ndarray] = []
        occ: List[float] = []
        it = 0
        t0 = time.perf_counter()
        while pending or any(slots):
            # 1) retire finished slots (host-side step counters — no sync)
            with spans.span(spans.SCHED_RETIRE):
                for i, s in enumerate(slots):
                    if s is not None and s.decoded >= s.req.max_new - 1:
                        _retire(i)
            # 2) admit into free slots; the pipeline enqueues behind the
            #    in-flight step — sender prefill overlaps receiver decode
            for i in range(cap):
                if not pending:
                    break
                if slots[i] is None:
                    req = pending.popleft()
                    with spans.span(spans.SCHED_ADMIT, rid=req.rid):
                        t_q = time.perf_counter() - t0
                        try:
                            tok1 = self._admit(req, state, i)
                        except _LADDER_ERRORS as e:
                            # quarantine, don't crash: the failing SENDER's
                            # admission is downgraded to text-only and the
                            # slot reused; in-flight rows never notice.
                            # (With a session ladder the share degrades
                            # internally and this path only fires for
                            # ladder-less sessions or a ladder whose every
                            # rung failed.)
                            ev = DegradationEvent(
                                stage="baseline",
                                reason=f"{type(e).__name__}: {e}",
                                attempts=getattr(e, "attempts", 1),
                                rid=req.rid)
                            sess.transport.log.append(TransferRecord(
                                kind="kv", n_bytes=0, layers=0,
                                context_len=0, wire_dtype="none",
                                attempts=ev.attempts, degradation=ev))
                            sess.degradations.append(ev)
                            tok1 = self._admit(req, state, i,
                                               force_baseline=True)
                        queued[req.rid] = (t_q, time.perf_counter() - t0)
                    first_tok[req.rid] = tok1
                    fetch_q.append((it, tok1, req.rid))
                    if req.max_new > 1:
                        slots[i] = _Slot(req=req, start_hist=len(history),
                                         col=i)
                    else:
                        done[req.rid] = _Slot(req=req,
                                              start_hist=len(history))
            # 3) one ragged iteration over the whole table
            if any(slots):
                with spans.span(spans.SCHED_STEP):
                    ntok, _, state["table"] = sess.receiver.ragged_step(
                        state["cur_tok"], state["table"], self.meta,
                        state["prefix_lens"], state["active"],
                        backend=cfgd.decode_backend)
                    state["cur_tok"] = ntok[:, None]
                    history.append(ntok)
                    live = sum(s is not None for s in slots)
                    occ.append(live / cap)
                    for s in slots:
                        if s is not None:
                            s.decoded += 1
            # 4) double buffering: materialize LAST iteration's results
            #    while this one executes; stamps TTFT one step late at most.
            #    The same lagged reads drive EOS-based early exit: a slot
            #    whose materialized token is the EOS retires here, so its
            #    row is readmitted next iteration instead of decoding out
            #    the full budget (detection lags one step — the wasted
            #    tokens are truncated from the completion below).
            with spans.span(spans.SCHED_READ):
                while fetch_q and fetch_q[0][0] < it:
                    _, arr, rid = fetch_q.popleft()
                    tok0 = int(np.asarray(arr)[0])
                    ttft.setdefault(rid, time.perf_counter() - t0)
                    if eos is not None and tok0 == eos:
                        for i, s in enumerate(slots):
                            if s is not None and s.req.rid == rid:
                                _retire(i)
                if len(history) >= 2:
                    h = np.asarray(history[-2])
                    if eos is not None:
                        row = len(history) - 2
                        for i, s in enumerate(slots):
                            if s is not None and row >= s.start_hist \
                                    and h[s.col] == eos:
                                _retire(i)
            # settle drained transfer stamps without blocking, so the
            # deferred log (which pins receiver views on device) stays
            # bounded by in-flight transfers, not stream length
            with spans.span(spans.SCHED_POLL):
                sess.transport.poll_latency()
            it += 1

        with spans.span(spans.SCHED_DRAIN):
            # drain: one host sync for everything still in flight
            hist = (np.asarray(jnp.stack(history)) if history
                    else np.zeros((0, cap), np.int32))
            now = time.perf_counter() - t0
            for _, arr, rid in fetch_q:
                np.asarray(arr)
                ttft.setdefault(rid, now)
            sess.transport.flush_latency()

            # per-request degradation events from this run (last per rid
            # wins)
            dmap: Dict[int, DegradationEvent] = {
                ev.rid: ev for ev in sess.degradations[n_deg0:]
                if ev.rid is not None}
            completions = []
            for rid in sorted(done):
                s = done[rid]
                toks = [int(np.asarray(first_tok[rid])[0])]
                if s.req.max_new > 1:
                    # the request's decode tokens live in its own slot
                    # column, at the s.decoded history rows it was live for
                    # (its full budget unless EOS retired it early — later
                    # rows of that column may already belong to a
                    # readmitted request)
                    toks.extend(hist[s.start_hist:
                                     s.start_hist + s.decoded, s.col]
                                .tolist())
                if eos is not None and eos in toks:
                    # EOS detection lags the lagged host read by a step or
                    # two; everything decoded past the EOS is dead weight
                    toks = toks[:toks.index(eos) + 1]
                q_s, adm_s = queued[rid]
                completions.append(Completion(
                    rid=rid, tokens=np.asarray(toks, np.int32),
                    ttft_s=ttft.get(rid, now), queue_s=q_s,
                    admitted_s=adm_s, degradation=dmap.get(rid)))
            return completions, {
                "iterations": it,
                "occupancy": float(np.mean(occ)) if occ else 0.0,
                # tokens actually DELIVERED (EOS truncation included) — the
                # honest numerator for any tokens/s derived from these stats
                "tokens": int(sum(len(c.tokens) for c in completions)),
            }


# ---------------------------------------------------------------------------
# the serial reference path
# ---------------------------------------------------------------------------
def serve_serial(session: CommSession, requests: Sequence[Request],
                 kvcfg: KVCommConfig, *, calib_key: Optional[str] = None,
                 eos_token: Optional[int] = None,
                 backend: str = "reference"
                 ) -> Tuple[List[Completion], Dict[str, float]]:
    """The pre-scheduler loop: one request at a time, every stage blocking
    (synced transport stamp, per-token streamed decode). This is the
    correctness reference the scheduler must match token-for-token, and
    the baseline ``benchmarks/serve_bench.py`` races.  ``eos_token`` stops
    a stream after emitting that token (the reference semantics for the
    scheduler's EOS-based early exit); ``backend`` picks the per-step
    decode attention impl ("reference" | "pallas")."""
    completions = []
    t0 = time.perf_counter()
    for req in sorted(requests, key=lambda r: r.rid):
        queue_s = time.perf_counter() - t0
        shared, _ = session.share(req.context[None, :], kvcfg,
                                  key=calib_key, sync=True, rid=req.rid)
        admitted_s = time.perf_counter() - t0
        degraded = session.last_degradation
        toks, ttft = [], 0.0
        for step_tok in session.stream(req.query[None, :], shared,
                                       max_new=req.max_new,
                                       backend=backend):
            if not toks:
                ttft = time.perf_counter() - t0
            toks.append(int(step_tok[0]))
            if eos_token is not None and toks[-1] == eos_token:
                break
        completions.append(Completion(
            rid=req.rid, tokens=np.asarray(toks, np.int32), ttft_s=ttft,
            queue_s=queue_s, admitted_s=admitted_s, degradation=degraded))
    return completions, {
        "iterations": sum(len(c.tokens) for c in completions),
        # one request at a time: the single implicit slot is always busy
        "occupancy": 1.0,
        "tokens": int(sum(len(c.tokens) for c in completions)),
    }


def accuracy(completions: Sequence[Completion],
             requests: Sequence[Request]) -> float:
    """Fraction of completions whose first token equals the request's
    recorded answer (single-token tasks)."""
    byrid = {r.rid: r for r in requests}
    hits = [c.pred == byrid[c.rid].answer for c in completions
            if byrid[c.rid].answer is not None]
    return float(np.mean(hits)) if hits else 0.0


def make_requests(task_batches, max_new: int = 8,
                  pad: Optional[int] = None) -> List[Request]:
    """Flatten task batches ({"context","query","answer"} dicts) into a
    per-request stream, trimming right-pad from contexts and left-pad from
    queries so every request carries its NATURAL lengths (the mixed-length
    stream continuous batching exists for)."""
    reqs: List[Request] = []
    for batch in task_batches:
        B = batch["context"].shape[0]
        for b in range(B):
            ctx, qry = batch["context"][b], batch["query"][b]
            if pad is not None:
                ctx = ctx[:int(np.max(np.nonzero(ctx != pad)[0])) + 1] \
                    if np.any(ctx != pad) else ctx[:1]
                qry = qry[int(np.min(np.nonzero(qry != pad)[0])):] \
                    if np.any(qry != pad) else qry[-1:]
            reqs.append(Request(rid=len(reqs), context=np.asarray(ctx),
                                query=np.asarray(qry), max_new=max_new,
                                answer=int(batch["answer"][b])))
    return reqs
