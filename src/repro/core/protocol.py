"""The KVComm communication protocol (paper §3.1), end to end.

Roles:
  sender_prefill    — M_s consumes the context C in ONE forward pass and
                      exports its per-layer KV (and SSM states, if any).
  calibrate         — M_r prefills the calibration query with ALL layers
                      shared and measures Eq. (1) attention masses.
  make_selection    — turns masses + KVCommConfig into the layer subset S.
  transmit          — builds the SharedKV the receiver consumes, and reports
                      exact wire bytes (the paper's communication cost).
  receiver_prefill  — M_r prefills Q with the sender prefix integrated.
  receiver_decode   — autoregressive generation from the merged cache.

All functions are pure and jit-friendly; the serving engine wraps them with
batching and scheduling.
"""
from __future__ import annotations

import collections
import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.selection import normalize_scores, select_layers
from repro.core.types import KVCommConfig, SharedKV
from repro.models import transformer as tfm

# Trace-count hook: each jitted entry point bumps its counter ONCE per
# compile (the Python body only runs while tracing), so tests can pin the
# no-retrace guarantee — e.g. one ragged decode-step compile per (selection
# bitmask, slot-table geometry), never per request.
TRACE_COUNTS: collections.Counter = collections.Counter()


# ---------------------------------------------------------------------------
# sender side
# ---------------------------------------------------------------------------
def extract_kv(cfg: ModelConfig, cache) -> Optional[Dict[str, jnp.ndarray]]:
    """Stack every attention layer's KV from a prefill cache:
    -> {"k","v"} of (L_attn, B, Sc, Hkv, Dh)."""
    ks, vs = [], []
    for spec, run in zip(cfg.layer_plan(), cache["runs"]):
        if spec.kind in ("attn", "shared_attn"):
            ks.append(run["k"])
            vs.append(run["v"])
    if not ks:
        return None
    return {"k": jnp.concatenate(ks, axis=0), "v": jnp.concatenate(vs, axis=0)}


def extract_states(cfg: ModelConfig, cache):
    """Stack SSM-layer final states -> pytree with leading L_ssm axis."""
    sts = [run for spec, run in zip(cfg.layer_plan(), cache["runs"])
           if spec.kind in ("mamba", "rwkv")]
    if not sts:
        return None
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *sts)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _sender_prefill_jit(params, cfg, context_tokens, extra):
    B, Sc = context_tokens.shape
    cache = tfm.init_cache(cfg, B, Sc)
    # only the cache leaves: never a (B, Sc, vocab) logits tensor, which at
    # a 128k vocabulary outweighs the exported KV
    out = tfm.apply_model(params, cfg, context_tokens, mode="cached",
                          cache=cache, extra=extra, logits_mode="last")
    return extract_kv(cfg, out.cache), extract_states(cfg, out.cache)


def sender_prefill(params, cfg: ModelConfig, context_tokens,
                   extra=None) -> Tuple[Dict[str, Any], Any]:
    """One forward pass of M_s over C. Returns (kv, states)."""
    return _sender_prefill_jit(params, cfg, context_tokens, extra)


# ---------------------------------------------------------------------------
# calibration + selection
# ---------------------------------------------------------------------------
def calibrate(receiver_params, cfg: ModelConfig, query_tokens,
              kv, states=None, extra=None) -> jnp.ndarray:
    """Prefill Q with EVERY layer shared, measuring Eq. (1) masses.

    Returns the normalized attention importance scores S_a, shape (L_attn,).
    A single calibration sample suffices (paper §H); pass a batch to average.
    """
    L = cfg.attn_layer_count
    Sc = kv["k"].shape[2]
    shared = SharedKV(
        kv=kv, select=jnp.ones((L,), bool),
        states=states,
        state_select=(jnp.ones((_n_ssm(cfg),), bool)
                      if states is not None else None),
        prefix_len=Sc)
    out = _receiver_prefill_jit(receiver_params, cfg, query_tokens, shared,
                                0, extra, collect_mass=True)
    return normalize_scores(out.masses)


def _n_ssm(cfg: ModelConfig) -> int:
    return sum(s.count for s in cfg.layer_plan()
               if s.kind in ("mamba", "rwkv"))


def make_selection(cfg: ModelConfig, kvcfg: KVCommConfig,
                   attn_scores: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    return select_layers(attn_scores, cfg.attn_layer_count, kvcfg)


# ---------------------------------------------------------------------------
# transmission
# ---------------------------------------------------------------------------
def build_shared(kvcfg: KVCommConfig, kv, select,
                 states=None, state_select=None) -> SharedKV:
    """Assemble the receiver-side ``SharedKV`` view (pure, jit-friendly —
    no byte accounting; that is the transport's job, see
    ``repro.comm.transport``).

    The view carries the full stack + mask so the uniform-scan receiver can
    consume it; a real wire sends only the gathered subset —
    ``gather_selected`` below materializes exactly that payload.
    """
    return SharedKV(
        kv=kv, select=select, states=states, state_select=state_select,
        prefix_len=0 if kv is None else kv["k"].shape[2],
        pos_mode=kvcfg.pos_mode)


def transmit(cfg: ModelConfig, kvcfg: KVCommConfig, kv, select,
             states=None, state_select=None) -> Tuple[SharedKV, int]:
    """Deprecated shim: ``build_shared`` + analytic byte count in one call.

    Byte accounting lives in ``repro.comm.transport`` (host-side, where the
    selected-layer count is static); this wrapper remains for legacy callers
    and forces ``select`` to a concrete value — do not call under ``jit``.
    """
    from repro.comm.transport import payload_bytes
    return (build_shared(kvcfg, kv, select, states, state_select),
            payload_bytes(kv, select, states, state_select))


def gather_selected(kv, select) -> Dict[str, jnp.ndarray]:
    """Materialize exactly the wire payload: the M selected layers' KV,
    gathered along the layer axis (what a real transport would move)."""
    idx = jnp.nonzero(select)[0]
    return {"k": kv["k"][idx], "v": kv["v"][idx]}


def selected_layer_ids(select) -> Tuple[int, ...]:
    """Host-side static tuple of selected attention-layer indices (the
    packed form's layer-index map). Forces ``select`` concrete — do not
    call under ``jit``."""
    if select is None:
        return ()
    return tuple(int(i) for i in np.nonzero(np.asarray(select))[0])


def build_packed(kvcfg: KVCommConfig, payload, layers: Sequence[int],
                 prefix_len: int, select=None, states=None,
                 state_select=None) -> SharedKV:
    """Assemble the packed receiver-side view from an already-gathered
    payload ({"k","v"} of (M, B, Sc, Hkv, Dh)) plus its static layer-index
    map — what a transport that moved exactly the wire bytes hands over."""
    layers = tuple(int(i) for i in layers)
    if select is None:
        raise ValueError("build_packed needs the (L,) selection mask so the "
                         "packed view can be densified / recombined")
    return SharedKV(packed_kv=payload, layers=layers,
                    select=jnp.asarray(select), states=states,
                    state_select=state_select, prefix_len=prefix_len,
                    pos_mode=kvcfg.pos_mode)


def pack_shared(kvcfg: KVCommConfig, kv, select,
                states=None, state_select=None) -> SharedKV:
    """``build_shared``'s selection-specialized sibling: gather the selected
    layers into the (M, B, Sc, Hkv, Dh) packed payload + static layer map.
    Host-side (the selection must be concrete) — exactly the transport's
    situation, where the selected-layer count is static anyway."""
    if kv is None:
        return build_shared(kvcfg, kv, select, states, state_select)
    layers = selected_layer_ids(select)
    idx = np.asarray(layers, np.int32)
    payload = {"k": kv["k"][idx], "v": kv["v"][idx]}
    return build_packed(kvcfg, payload, layers, int(kv["k"].shape[2]),
                        select=select, states=states,
                        state_select=state_select)


# ---------------------------------------------------------------------------
# heterogeneous transmission (sender depth != receiver depth)
# ---------------------------------------------------------------------------
def gather_mapped(kv, assignment) -> Dict[str, jnp.ndarray]:
    """The heterogeneous wire payload: the sender layers named by
    ``assignment.src``, gathered in receiver-slot (``dst``) order —
    (P, B, Sc, Hkv, Dh). Host-side static indices."""
    idx = np.asarray(assignment.src, np.int32)
    return {"k": kv["k"][idx], "v": kv["v"][idx]}


def build_mapped(kvcfg: KVCommConfig, payload, assignment, prefix_len: int,
                 states=None, state_select=None) -> SharedKV:
    """Packed receiver-side view from an already-gathered mapped payload:
    ``layers`` carries the RECEIVER slots (what the selection-specialized
    cache partitions on), ``src_layers`` the sender provenance. Everything
    the fast path consumes is receiver-keyed, so a mapped SharedKV rides
    the same packed machinery as a homogeneous one."""
    return SharedKV(packed_kv=payload, layers=tuple(assignment.dst),
                    src_layers=tuple(assignment.src),
                    select=jnp.asarray(assignment.dst_mask()),
                    states=states, state_select=state_select,
                    prefix_len=prefix_len, pos_mode=kvcfg.pos_mode)


def pack_mapped(kvcfg: KVCommConfig, kv, assignment,
                states=None, state_select=None) -> SharedKV:
    """``pack_shared`` for a heterogeneous pair: gather the assignment's
    sender layers and key the packed view by receiver slot."""
    if kv is None:
        return build_shared(kvcfg, kv,
                            jnp.asarray(assignment.dst_mask()),
                            states, state_select)
    return build_mapped(kvcfg, gather_mapped(kv, assignment), assignment,
                        int(kv["k"].shape[2]), states=states,
                        state_select=state_select)


def scatter_mapped(kvcfg: KVCommConfig, payload, assignment,
                   prefix_len: int, states=None,
                   state_select=None) -> SharedKV:
    """Dense receiver-side view of a mapped payload: a zero-padded
    (L_dst, ...) stack with each packed slice scattered into its receiver
    slot (the uniform-scan fallback path; ``select`` masks the zeros)."""
    idx = np.asarray(assignment.dst, np.int32)
    kv = {}
    for part in ("k", "v"):
        p = payload[part]
        dense = jnp.zeros((assignment.num_dst_layers,) + tuple(p.shape[1:]),
                          p.dtype)
        if assignment.num_pairs:
            dense = dense.at[idx].set(p)
        kv[part] = dense
    return SharedKV(kv=kv, select=jnp.asarray(assignment.dst_mask()),
                    states=states, state_select=state_select,
                    prefix_len=prefix_len, pos_mode=kvcfg.pos_mode)


# ---------------------------------------------------------------------------
# receiver side
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new", "collect_mass"))
def _receiver_prefill_jit(params, cfg, query_tokens, shared, max_new,
                          extra, collect_mass=False, prefix_lens=None):
    TRACE_COUNTS["receiver_prefill"] += 1
    B, Sq = query_tokens.shape
    cache = tfm.init_cache(cfg, B, Sq + max_new, shared=shared)
    return tfm.apply_model(params, cfg, query_tokens, mode="cached",
                           cache=cache, shared=shared, extra=extra,
                           collect_mass=collect_mass,
                           prefix_lens=prefix_lens)


def receiver_prefill(params, cfg: ModelConfig, query_tokens,
                     shared: Optional[SharedKV], max_new: int = 64,
                     extra=None, prefix_lens=None):
    """Prefill Q with the sender prefix integrated; cache sized for decode.

    ``prefix_lens`` (per-row (B,) int32) marks each row's REAL prefix
    length when ``shared`` was bucket-padded (``pad_prefix``): the pad tail
    is masked out of attention and self positions continue from the real
    length, so a padded prefill answers exactly like an unpadded one."""
    return _receiver_prefill_jit(params, cfg, query_tokens, shared,
                                 max_new, extra, prefix_lens=prefix_lens)


def receiver_decode(params, cfg: ModelConfig, token, cache,
                    shared: Optional[SharedKV] = None):
    """One greedy decode step, eager (op-by-op dispatch). token: (B, 1).

    The serving path is ``decode_step`` below — one compiled call per token
    with the cache donated; this stays as the reference implementation and
    the benchmark's eager baseline."""
    out = tfm.apply_model(params, cfg, token, mode="cached", cache=cache,
                          shared=shared, logits_mode="last")
    return out


# decode-step attention implementations selectable per call (static under
# jit, so each backend compiles its own step and TRACE_COUNTS pins both the
# aggregate and the per-backend key)
DECODE_BACKENDS = ("reference", "pallas")


def _check_backend(backend: str) -> None:
    if backend not in DECODE_BACKENDS:
        raise ValueError(
            f"unknown decode backend {backend!r}; expected one of "
            f"{DECODE_BACKENDS}")


@functools.partial(jax.jit, static_argnames=("cfg", "backend"),
                   donate_argnums=(3,))
def _decode_step_jit(params, cfg, token, cache, shared,
                     backend="reference"):
    TRACE_COUNTS["decode_step"] += 1
    TRACE_COUNTS[f"decode_step[{backend}]"] += 1
    out = tfm.apply_model(params, cfg, token, mode="cached", cache=cache,
                          shared=shared, logits_mode="last",
                          decode_backend=backend)
    next_tok = jnp.argmax(out.logits[:, -1, :], axis=-1)
    return next_tok, out.logits[:, -1, :], out.cache


def decode_step(params, cfg: ModelConfig, token, cache,
                shared: Optional[SharedKV] = None,
                backend: str = "reference"):
    """One greedy decode step as ONE compiled call with the cache donated
    (``donate_argnums``): steady-state decode re-uses the cache buffers
    in place instead of materializing a fresh KV stack every token (on
    backends that implement donation; elsewhere it degrades gracefully).

    The caller must treat the passed ``cache`` as consumed. ``shared`` is
    reduced to its payload-free ``meta()`` view — the prefix already lives
    in the cache — so per-step transfers are just the token.

    ``backend`` picks the attention implementation of the step:
    ``"reference"`` is the masked-dense oracle, ``"pallas"`` the fused
    ragged kernel (``kernels.ragged_decode``).

    Returns (next_token (B, 1), last_logits (B, V), new_cache).
    """
    _check_backend(backend)
    meta = shared.meta() if shared is not None else None
    next_tok, logits, cache = _decode_step_jit(params, cfg,
                                               jnp.asarray(token), cache,
                                               meta, backend=backend)
    return next_tok[:, None], logits, cache


def pad_prefix(shared: SharedKV, prefix_len: int) -> SharedKV:
    """Zero-pad the shared prefix along Sc up to the bucket ``prefix_len``.

    The pad region ``[shared.prefix_len, prefix_len)`` is masked out of
    receiver attention by per-row ``prefix_lens`` (see ``receiver_prefill``
    / ``ragged_decode_step``), so the fill value is never read — padding
    exists purely so every request in a continuous-batching slot table
    shares one compiled cache geometry. Works on packed and dense views."""
    if shared.prefix_len == prefix_len:
        return shared
    assert shared.prefix_len < prefix_len, \
        f"cannot shrink a prefix ({shared.prefix_len} -> {prefix_len})"
    pad = prefix_len - shared.prefix_len

    def pad_kv(kvd):
        if kvd is None:
            return None
        return {p: jnp.pad(kvd[p],
                           ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                for p in ("k", "v")}

    return SharedKV(kv=pad_kv(shared.kv), select=shared.select,
                    states=shared.states, state_select=shared.state_select,
                    prefix_len=prefix_len, pos_mode=shared.pos_mode,
                    packed_kv=pad_kv(shared.packed_kv),
                    layers=shared.layers, src_layers=shared.src_layers)


@functools.partial(jax.jit, static_argnames=("cfg", "backend"),
                   donate_argnums=(3,))
def _ragged_decode_step_jit(params, cfg, tokens, cache, shared,
                            prefix_lens, active, backend="reference"):
    TRACE_COUNTS["ragged_decode_step"] += 1
    TRACE_COUNTS[f"ragged_decode_step[{backend}]"] += 1
    out = tfm.apply_model(params, cfg, tokens, mode="cached", cache=cache,
                          shared=shared, logits_mode="last",
                          prefix_lens=prefix_lens, decode_backend=backend)
    cache = out.cache
    # finished/empty rows do not advance: their length (and therefore their
    # write cursor) is frozen, so a dead slot rewrites its own masked
    # position forever instead of walking off the buffer, and live rows —
    # batch-independent throughout the model — never see them
    cache["len"] = jnp.where(active, cache["len"], cache["len"] - 1)
    logits = out.logits[:, -1, :]
    return jnp.argmax(logits, axis=-1), logits, cache


def ragged_decode_step(params, cfg: ModelConfig, tokens, cache,
                       shared: Optional[SharedKV], prefix_lens, active,
                       backend: str = "reference"):
    """One continuous-batching iteration over a slot-table cache.

    ``cache`` is a B==capacity serving cache whose per-row ``len`` tracks
    each slot's own write cursor (requests sit at different generation
    offsets); ``prefix_lens`` (capacity,) carries per-row REAL prefix
    lengths inside the bucket and ``active`` (capacity,) masks live slots.
    ONE donated compiled call advances every live row by one token —
    specialization is per (frozen selection, table geometry, backend),
    never per request. ``backend`` dispatches the step's attention:
    ``"reference"`` keeps the masked-dense parity oracle, ``"pallas"``
    runs the fused two-segment kernel (``kernels.ragged_decode``) that
    consumes the table's per-row ``kv_len``/``prefix_lens`` directly.
    Returns (next_tokens (capacity,), logits, new cache); ``cache`` is
    consumed.
    """
    _check_backend(backend)
    meta = shared.meta() if shared is not None else None
    return _ragged_decode_step_jit(params, cfg, jnp.asarray(tokens), cache,
                                   meta, jnp.asarray(prefix_lens),
                                   jnp.asarray(active), backend=backend)


def generate(params, cfg: ModelConfig, query_tokens, shared=None,
             max_new: int = 32, extra=None, stop_token: int = -1):
    """Greedy generation. Returns (tokens (B, max_new), final cache).

    Prefill and the whole decode scan are ONE jitted program, compiled once
    per (query shape, prefix layout, max_new) — a served path calls this
    per request, and re-tracing the scan each time would recompile it."""
    return _generate_jit(params, cfg, query_tokens, shared, extra,
                         max_new=max_new)


@functools.partial(jax.jit, static_argnames=("cfg", "max_new"))
def _generate_jit(params, cfg, query_tokens, shared, extra, max_new):
    out = receiver_prefill(params, cfg, query_tokens, shared,
                           max_new=max_new, extra=extra)
    cache = out.cache
    next_tok = jnp.argmax(out.logits[:, -1, :], axis=-1)[:, None]

    def step(carry, _):
        cache, tok = carry
        o = receiver_decode(params, cfg, tok, cache, shared)
        nt = jnp.argmax(o.logits[:, -1, :], axis=-1)[:, None]
        return (o.cache, nt), tok[:, 0]

    (cache, _), toks = jax.lax.scan(step, (cache, next_tok), None,
                                    length=max_new)
    return jnp.moveaxis(toks, 0, 1), cache
