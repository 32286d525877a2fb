"""CommSession: a sender/receiver pairing over a transport.

The session is the stateful piece of the stack: it owns

  * calibration state — Eq. (1) scores and frozen layer selections, cached
    per (task key, KVCommConfig) so a selection calibrated once is reused
    across every batch of that task (the paper's "one sample suffices", §H);
  * the transport — every KV transfer is byte-accounted in one log;
  * multi-sender composition (§J) — extra senders attach via
    ``attach_sender`` and deposit SharedKV views into a mailbox that
    ``combined()`` merges with ``combine_senders``;
  * heterogeneous pairs — sender and receiver may disagree on depth:
    ``calibrate_side``/``side_selection`` score each model over its own
    L_attn and ``share_mapped`` aligns them with a ``LayerMap`` policy;
  * batched and streaming generation on the receiver.

``session.run(method, batch, ...)`` dispatches through the ``METHODS``
registry — the replacement for the old 200-line ``CommEngine.run`` if-chain.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.comm.agent import Agent
from repro.comm.methods import CommRequest, MethodResult, get_method
from repro.comm.remote import RemoteProtocolError
from repro.comm.resilience import DegradationEvent, Resilience
from repro.comm.transport import InMemoryTransport, Transport
from repro.core.channel import TransferRecord, combine_senders
from repro.core.types import KVCommConfig, SharedKV
from repro.utils import spans

# what the degradation ladder can catch: transport/protocol failures (incl.
# RetriesExhaustedError and CircuitOpenError) and raw socket errors — never
# programming errors, which propagate
_LADDER_ERRORS = (RemoteProtocolError, OSError)


@dataclass
class SenderHandle:
    """A registered extra sender. ``send`` prefills its context, pushes the
    selected KV through the session transport, and deposits the receiver-side
    view in the session mailbox (mailbox-style multi-sender composition)."""
    session: "CommSession"
    agent: Agent
    name: str

    def send(self, context: np.ndarray, kvcfg: KVCommConfig,
             select: Optional[jnp.ndarray] = None,
             scores: Optional[jnp.ndarray] = None,
             calib_key: Optional[str] = None) -> SharedKV:
        sess = self.session
        # mailbox composition indexes this sender's KV with receiver-keyed
        # selections (and seeds SSM states positionally) — only sound when
        # depths agree (mapped multi-sender composition is a ROADMAP
        # follow-up)
        from repro.core.protocol import _n_ssm
        assert (self.agent.cfg.attn_layer_count
                == sess.cfg.attn_layer_count
                and _n_ssm(self.agent.cfg) == _n_ssm(sess.cfg)), \
            "multi-sender mailbox needs sender depth == receiver depth"
        if select is None:
            # thread the task key so extra senders reuse the task's frozen
            # selection instead of recomputing from prior-only scores
            select = sess.selection(kvcfg, scores=scores, key=calib_key)
        kv, states, _ = self.agent.export_kv(context)
        state_select = sess._state_selection(kvcfg, states)
        shared = sess.transport.send(sess.cfg, kvcfg, kv, select,
                                     states, state_select)
        sess.mailbox.append((self.name, shared))
        return shared


class CommSession:
    """Holds calibration state, frozen selections, the transport log, and
    the (possibly >1) senders talking to one receiver."""

    def __init__(self, sender: Agent, receiver: Agent,
                 transport: Optional[Transport] = None,
                 resilience: Optional[Resilience] = None):
        scfg, rcfg = sender.cfg, receiver.cfg
        if scfg.supports_kv_sharing and rcfg.supports_kv_sharing:
            # depths may differ (a LayerMap aligns them) but the per-layer
            # KV geometry must match for the receiver to consume it raw
            assert (scfg.num_kv_heads == rcfg.num_kv_heads and
                    scfg.resolved_head_dim == rcfg.resolved_head_dim), \
                "sender/receiver must agree on KV geometry " \
                f"(Hkv, Dh): {(scfg.num_kv_heads, scfg.resolved_head_dim)}" \
                f" vs {(rcfg.num_kv_heads, rcfg.resolved_head_dim)}"
        self.sender = sender
        self.receiver = receiver
        self.transport = transport if transport is not None \
            else InMemoryTransport()
        self.cfg = receiver.cfg
        self._score_cache: Dict[Optional[str], jnp.ndarray] = {}
        self._sel_cache: Dict[Tuple[Optional[str], KVCommConfig],
                              jnp.ndarray] = {}
        # per-side state for heterogeneous pairs: scores/selections keyed
        # by ("sender"|"receiver", task key), each over that side's L_attn
        self._side_scores: Dict[Tuple[str, Optional[str]], jnp.ndarray] = {}
        self._side_sel: Dict[Tuple[str, Optional[str], KVCommConfig],
                             jnp.ndarray] = {}
        self.mailbox: List[Tuple[str, SharedKV]] = []
        self._n_handles = 0
        # graceful degradation (repro.comm.resilience): when set, a share
        # whose transport exhausts its retries walks the fallback ladder
        # instead of raising; every downgrade lands in ``degradations``
        self.resilience = resilience
        self.degradations: List[DegradationEvent] = []
        self.last_degradation: Optional[DegradationEvent] = None

    @property
    def is_hetero(self) -> bool:
        """True when sender and receiver disagree on attention OR SSM
        depth — the classic same-index protocol (``share``/"kvcomm") no
        longer applies and a ``LayerMap`` must align the sides
        (``share_mapped``/"hetero_kvcomm"; state sharing is positional,
        so a mismatched SSM depth alone also routes there, where states
        are dropped)."""
        from repro.core.protocol import _n_ssm
        scfg, rcfg = self.sender.cfg, self.receiver.cfg
        return (scfg.attn_layer_count != rcfg.attn_layer_count
                or _n_ssm(scfg) != _n_ssm(rcfg))

    def _agent(self, side: str) -> Agent:
        assert side in ("sender", "receiver"), side
        return self.sender if side == "sender" else self.receiver

    # ---- calibration + frozen selections ---------------------------------
    def calibrate(self, context: np.ndarray, query: np.ndarray,
                  key: Optional[str] = None) -> jnp.ndarray:
        """Eq. (1) scores from one calibration sample; cached under ``key``
        (a task identifier) so repeated batches skip the extra prefills.
        Cross-model: the receiver consumes the SENDER's KV, so both sides
        must agree on depth — heterogeneous pairs use ``calibrate_side``."""
        assert not self.is_hetero, \
            "cross-model calibration needs equal depths; " \
            "use calibrate_side('sender', ...) on a heterogeneous pair"
        if key is not None and key in self._score_cache:
            return self._score_cache[key]
        kv, states, _ = self.sender.export_kv(context)
        scores = self.receiver.calibrate(query, kv, states)
        if key is not None:
            self._score_cache[key] = scores
        return scores

    def calibrate_side(self, side: str, context: np.ndarray,
                       query: np.ndarray,
                       key: Optional[str] = None) -> jnp.ndarray:
        """Per-side Eq. (1) scores: ``side``'s agent self-calibrates
        (consumes its OWN exported KV), yielding scores over its own
        L_attn regardless of what the other side looks like. Cached under
        (side, key)."""
        cache_key = (side, key)
        if key is not None and cache_key in self._side_scores:
            return self._side_scores[cache_key]
        scores = self._agent(side).self_scores(context, query)
        if key is not None:
            self._side_scores[cache_key] = scores
        return scores

    def side_selection(self, side: str, kvcfg: KVCommConfig,
                       scores: Optional[jnp.ndarray] = None,
                       key: Optional[str] = None) -> jnp.ndarray:
        """The frozen layer subset over ``side``'s own L_attn — the
        per-side analogue of ``selection`` (same caching discipline:
        explicit scores recompute and refresh; score-less calls serve the
        frozen mask)."""
        agent = self._agent(side)
        cache_key = (side, key, kvcfg)
        if scores is None and key is not None:
            if cache_key in self._side_sel:
                return self._side_sel[cache_key]
            scores = self._side_scores.get((side, key))
        select = core.make_selection(agent.cfg, kvcfg, scores)
        if key is not None:
            self._side_sel[cache_key] = select
        return select

    def selection(self, kvcfg: KVCommConfig,
                  scores: Optional[jnp.ndarray] = None,
                  key: Optional[str] = None) -> jnp.ndarray:
        """The frozen layer subset S for (task key, kvcfg) — computed once,
        then reused for every batch (replaces CommEngine._sel_cache).
        Explicitly passed ``scores`` always recompute (and refresh the
        cache); the frozen selection serves only score-less calls."""
        cache_key = (key, kvcfg)
        if scores is None and key is not None:
            if cache_key in self._sel_cache:
                return self._sel_cache[cache_key]
            scores = self._score_cache.get(key)
        select = core.make_selection(self.cfg, kvcfg, scores)
        if key is not None:
            self._sel_cache[cache_key] = select
        return select

    def wire_plan(self, kvcfg: KVCommConfig,
                  scores: Optional[jnp.ndarray] = None,
                  key: Optional[str] = None,
                  top_frac: float = 0.25,
                  low_frac: float = 0.5) -> "WirePlan":
        """The adaptive per-layer wire precision for (task key, kvcfg):
        rank the FROZEN selection's layers by the same Eq. (1) calibration
        scores (+ depth prior) that chose them, then tier the wire —
        fp16 for the top ``top_frac``, int4 for the bottom ``low_frac``,
        int8 between.  Pass the result (or its ``"plan:..."`` spec)
        anywhere a ``wire_dtype`` goes (``SerializedTransport``,
        ``RemoteTransport``, the paged store).  Uses the cached
        calibration scores under ``key`` when ``scores`` is None; with no
        scores at all, the Gaussian depth prior alone ranks the layers
        (exactly how a prior_only selection was chosen)."""
        from repro.comm.transport import WirePlan
        select = self.selection(kvcfg, scores=scores, key=key)
        if scores is None and key is not None:
            scores = self._score_cache.get(key)
        n = int(np.asarray(select).shape[0])
        combined = (core.gaussian_prior(n, kvcfg.mu, kvcfg.sigma)
                    if scores is None
                    else core.selection_scores(jnp.asarray(scores), kvcfg))
        return WirePlan.from_scores(np.asarray(combined),
                                    select=np.asarray(select),
                                    top_frac=top_frac, low_frac=low_frac)

    def _state_selection(self, kvcfg: KVCommConfig, states):
        """SSM layers have no attention mass — share by depth prior."""
        if states is None:
            return None
        import dataclasses
        n_ssm = jax.tree.leaves(states)[0].shape[0]
        return core.select_layers(
            None, n_ssm, dataclasses.replace(kvcfg, selector="prior_only"))

    # ---- one communication round -----------------------------------------
    def _resilient_send(self, kvcfg: KVCommConfig, kv, select, states,
                        state_select, *, assignment=None,
                        sync: Optional[bool] = None,
                        rid: Optional[int] = None) -> Optional[SharedKV]:
        """Push one transfer through the primary transport, walking the
        ``Resilience`` fallback ladder when it fails.

        The healthy path is exactly ``transport.send``.  With a resilience
        config, an exhausted/failed primary send (or an open circuit —
        quarantine skips the doomed attempt entirely) tries each fallback
        rung in order; a rung with a transport serves the SAME payload
        in-process, the terminal ``("baseline", None)`` rung serves the
        request text-only (returns None — zero KV bytes).  Either way the
        downgrade is recorded: a ``DegradationEvent`` lands in
        ``self.degradations`` / ``self.last_degradation`` and on the
        ``TransferRecord`` appended to the PRIMARY transport's log (the
        single source of byte accounting; fallback rungs' records are
        moved there)."""
        self.last_degradation = None
        res = self.resilience
        if res is None:
            return self.transport.send(self.cfg, kvcfg, kv, select, states,
                                       state_select, assignment=assignment,
                                       sync=sync)
        failure: Optional[BaseException] = None
        if res.breaker is None or res.breaker.allow():
            try:
                shared = self.transport.send(
                    self.cfg, kvcfg, kv, select, states, state_select,
                    assignment=assignment, sync=sync)
                if res.breaker is not None:
                    res.breaker.record_success()
                return shared
            except _LADDER_ERRORS as e:
                failure = e
                if res.breaker is not None:
                    res.breaker.record_failure()
        else:
            from repro.comm.resilience import CircuitOpenError
            failure = CircuitOpenError(
                "sender quarantined: circuit open after "
                f"{res.breaker.failures} consecutive failures")
        attempts = getattr(failure, "attempts", 1)
        reason = f"{type(failure).__name__}: {failure}"
        for stage, tr in res.fallbacks:
            if tr is None:
                ev = DegradationEvent(stage="baseline", reason=reason,
                                      attempts=attempts, rid=rid)
                # a zero-byte record so the transfer log stays one row per
                # request and dedup/byte summaries see the degraded send
                self.transport.log.append(TransferRecord(
                    kind="kv", n_bytes=0, layers=0, context_len=0,
                    wire_dtype="none", attempts=attempts, degradation=ev))
                self.degradations.append(ev)
                self.last_degradation = ev
                return None
            try:
                # synced on purpose: the degraded rung is off the hot path
                # and must not park deferred stamps on a log nobody flushes
                shared = tr.send(self.cfg, kvcfg, kv, select, states,
                                 state_select, assignment=assignment,
                                 sync=True)
            except _LADDER_ERRORS as e:
                reason = f"{reason}; then {stage}: {type(e).__name__}: {e}"
                continue
            ev = DegradationEvent(stage=stage, reason=reason,
                                  attempts=attempts, rid=rid)
            rec = tr.log.pop()
            rec.degradation = ev
            self.transport.log.append(rec)
            self.degradations.append(ev)
            self.last_degradation = ev
            return shared
        raise failure       # ladder had no terminal baseline rung

    def share(self, context: np.ndarray, kvcfg: KVCommConfig,
              scores: Optional[jnp.ndarray] = None,
              key: Optional[str] = None,
              sync: Optional[bool] = None,
              rid: Optional[int] = None
              ) -> Tuple[Optional[SharedKV], jnp.ndarray]:
        """Primary-sender round: prefill the context, select layers, push
        through the transport. Returns (receiver-side SharedKV, select).
        ``sync=False`` keeps the whole round async-dispatched (no host
        block; the transfer latency stamp is deferred — the serving
        scheduler's hot path).

        With a ``resilience`` config the round degrades instead of
        raising: the SharedKV may come from a fallback transport, or be
        None (text-only baseline — callers pass it straight to
        ``stream``/``generate``); check ``last_degradation``.  ``rid``
        tags the resulting DegradationEvent with the caller's request
        id."""
        assert not self.is_hetero, \
            "sender and receiver disagree on depth; use share_mapped " \
            "(or the 'hetero_kvcomm' method) with a LayerMap policy"
        with spans.span(spans.SHARE, rid=rid):
            select = self.selection(kvcfg, scores=scores, key=key)
            kv, states, _ = self.sender.export_kv(context)
            state_select = self._state_selection(kvcfg, states)
            shared = self._resilient_send(kvcfg, kv, select, states,
                                          state_select, sync=sync, rid=rid)
        return shared, select

    def share_mapped(self, context: np.ndarray, kvcfg: KVCommConfig,
                     policy: str = "depth_proportional",
                     src_scores: Optional[jnp.ndarray] = None,
                     dst_scores: Optional[jnp.ndarray] = None,
                     key: Optional[str] = None,
                     sync: Optional[bool] = None,
                     rid: Optional[int] = None
                     ) -> Tuple[Optional[SharedKV], "core.LayerAssignment"]:
        """Heterogeneous-sender round: selection runs on the SENDER side
        over its own L_attn, the ``policy`` LayerMap places the selected
        layers into receiver slots, and the transport moves exactly the
        mapped payload. Works on homogeneous pairs too (where
        policy='identity' reproduces ``share`` bit-for-bit).

        Returns (receiver-side SharedKV, the LayerAssignment used)."""
        with spans.span(spans.SHARE, rid=rid):
            src_select = self.side_selection("sender", kvcfg,
                                             scores=src_scores, key=key)
            if src_scores is None and key is not None:
                src_scores = self._side_scores.get(("sender", key))
            if dst_scores is None and key is not None:
                dst_scores = self._side_scores.get(("receiver", key))
            src_layers = core.selected_layer_ids(src_select)
            assignment = core.get_layer_map(policy).assign(
                src_layers,
                num_src_layers=self.sender.cfg.attn_layer_count,
                num_dst_layers=self.receiver.cfg.attn_layer_count,
                src_scores=(None if src_scores is None
                            else np.asarray(src_scores)),
                dst_scores=(None if dst_scores is None
                            else np.asarray(dst_scores)))
            kv, states, _ = self.sender.export_kv(context)
            if states is not None:
                # SSM state sharing is positional (no mapping policy yet):
                # only possible when both sides agree on SSM depth
                from repro.core.protocol import _n_ssm
                n_ssm = jax.tree.leaves(states)[0].shape[0]
                if n_ssm != _n_ssm(self.receiver.cfg):
                    states = None
            state_select = self._state_selection(kvcfg, states)
            shared = self._resilient_send(kvcfg, kv, None, states,
                                          state_select, assignment=assignment,
                                          sync=sync, rid=rid)
            return shared, assignment

    # ---- multi-sender (§J) ------------------------------------------------
    def attach_sender(self, agent: Agent,
                      name: Optional[str] = None) -> SenderHandle:
        """Register an additional sender; returns its mailbox handle."""
        handle = SenderHandle(self, agent,
                              name or f"{agent.name}#{self._n_handles}")
        self._n_handles += 1
        return handle

    def combined(self, clear: bool = False) -> SharedKV:
        """Merge every mailbox deposit along the context axis
        (``combine_senders``: one joint selection covers all prefixes)."""
        assert self.mailbox, "no sender has deposited a SharedKV yet"
        merged = combine_senders([s for _, s in self.mailbox])
        if clear:
            self.mailbox.clear()
        return merged

    # ---- paged-store accounting -------------------------------------------
    def dedup_summary(self) -> Dict[str, float]:
        """Aggregate the transport log's paged-transfer dedup accounting:
        how many pages the session's transfers referenced, how many
        actually crossed, and the pool-hit rate.  Zeroes (and 0 transfers)
        when no ``PageStore`` is attached — unpaged records carry no page
        counts."""
        recs = [r for r in self.transport.log if r.pages_total]
        total = sum(r.pages_total for r in recs)
        sent = sum(r.pages_sent for r in recs)
        hit = sum(r.pages_hit for r in recs)
        return {
            "transfers": len(recs),
            "pages_total": total,
            "pages_sent": sent,
            "pages_hit": hit,
            "hit_rate": (hit / total) if total else 0.0,
            "bytes": sum(r.n_bytes for r in recs),
        }

    # ---- dispatch ---------------------------------------------------------
    def run(self, method: str, batch: Dict[str, np.ndarray],
            kvcfg: Optional[KVCommConfig] = None,
            scores: Optional[jnp.ndarray] = None,
            ac_layer: Optional[int] = None,
            nld_tokens: int = 16,
            max_new: int = 1,
            calib_key: Optional[str] = None,
            layer_map: str = "depth_proportional") -> MethodResult:
        """Run one registered method over a batch. Thin registry lookup —
        the signature mirrors the legacy ``CommEngine.run`` (plus
        ``layer_map``, the policy 'hetero_kvcomm' aligns depths with)."""
        req = CommRequest(kvcfg=kvcfg, scores=scores, ac_layer=ac_layer,
                          nld_tokens=nld_tokens, max_new=max_new,
                          calib_key=calib_key, layer_map=layer_map)
        t0 = time.perf_counter()
        result = get_method(method).run(self, batch, req)
        # wall clock around async JAX dispatch measures enqueue, not
        # compute: sync everything the method produced before stopping
        # the timer (preds are host numpy already; extras may not be)
        jax.block_until_ready((result.preds, result.extras))
        result.latency_s = time.perf_counter() - t0
        return result

    # ---- generation -------------------------------------------------------
    def generate(self, query: np.ndarray, shared: Optional[SharedKV] = None,
                 max_new: int = 32) -> np.ndarray:
        """Batched greedy generation on the receiver. (B, max_new) tokens."""
        toks, _ = self.receiver.generate(query, shared, max_new=max_new)
        return np.asarray(toks)

    def stream(self, query: np.ndarray, shared: Optional[SharedKV] = None,
               max_new: int = 32,
               backend: str = "reference") -> Iterator[np.ndarray]:
        """Streaming greedy generation: yields one (B,) token per step (the
        serving path — first token after prefill, then step-wise decode).

        Each step is one compiled call with the cache donated
        (``core.decode_step``): steady-state decode updates the cache in
        place instead of re-materializing it per token. ``backend`` picks
        the per-step attention impl ("reference" | "pallas")."""
        if max_new <= 0:
            return
        out = self.receiver.prefill(query, shared, max_new=max_new)
        cache = out.cache
        tok = jnp.argmax(out.logits[:, -1, :], axis=-1)[:, None]
        yield np.asarray(tok[:, 0])
        for _ in range(max_new - 1):
            tok, _, cache = self.receiver.decode_step(tok, cache, shared,
                                                      backend=backend)
            yield np.asarray(tok[:, 0])
