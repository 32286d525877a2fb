"""GQA attention block with first-class KVComm support.

Execution modes:

  * ``train``  — full causal self-attention over S tokens, no cache.
  * ``cached`` — S new tokens (prefill S>1, decode S==1) appended into a
                 fixed-size cache buffer laid out::

                     [ sender prefix (prefix_len) | self tokens ... | pad ]

KVComm specifics
----------------
The sender's transmitted KV occupies cache positions ``[0, prefix_len)``.
``ctx_valid`` (a per-layer scalar bool threaded through the layer scan) masks
the prefix out at non-selected layers — numerically identical to never
concatenating it (softmax over -1e30), which lets the paper's non-contiguous
layer selections run under a uniform ``lax.scan``.  The packed fast path
(``transformer._apply_packed_attn_run``) instead calls this block with
``prefix_len == 0`` for unselected sub-scans — no prefix buffer, no masking,
attention FLOPs scale with the selection ratio.

Positional coherence (paper §K): receiver tokens live at absolute positions
``pos_shift + j``. The paper's default sets ``pos_shift == prefix_len`` at
*every* layer; the KVComm-S ablation zeroes it on non-selected layers, hence
it is a per-layer traced scalar. Sender K arrives already rotated at positions
``[0, prefix_len)`` from the sender's own prefill.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import (attention_core, attention_core_chunked,
                                 dense_init, rope)


def _core(cfg):
    """Attention execution strategy: "xla" materializes (Sq, Skv) probs;
    "chunked" scans query blocks (memory-efficient, the deployment default
    for long shapes — §Perf iteration 1)."""
    if cfg.attn_impl == "chunked":
        import functools
        return functools.partial(attention_core_chunked,
                                 blk_q=cfg.attn_block_q)
    return attention_core


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def init_attn(key, cfg, *, d_model=None):
    d = d_model or cfg.d_model
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, Hq * Dh), _dt(cfg)),
        "wk": dense_init(ks[1], (d, Hkv * Dh), _dt(cfg)),
        "wv": dense_init(ks[2], (d, Hkv * Dh), _dt(cfg)),
        "wo": dense_init(ks[3], (Hq * Dh, d), _dt(cfg)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq * Dh,), _dt(cfg))
        p["bk"] = jnp.zeros((Hkv * Dh,), _dt(cfg))
        p["bv"] = jnp.zeros((Hkv * Dh,), _dt(cfg))
    return p


def _proj(p, x, name, cfg, H, Dh):
    y = x @ p[f"w{name}"]
    if cfg.qkv_bias and f"b{name}" in p:
        y = y + p[f"b{name}"]
    B, S, _ = x.shape
    return y.reshape(B, S, H, Dh)


def self_attention(
    p, cfg, x, *,
    mode: str,                              # "train" | "cached"
    causal: bool = True,
    use_rope: bool = True,
    window: Optional[int] = None,           # static per layer-run
    pos_shift,                              # scalar or (B,) (traced): offset
    prefix_len: int = 0,                    # static: sender prefix length
                                            # (the BUFFER size; per-row real
                                            # lengths ride in prefix_lens)
    ctx_valid: Optional[jnp.ndarray] = None,  # scalar bool: layer selected?
    cache_k: Optional[jnp.ndarray] = None,  # (B, Smax, Hkv, Dh), or the
    cache_v: Optional[jnp.ndarray] = None,  # run's (m, ...) stack with
    cache_layer=None,                       # this (traced) layer index:
                                            # written and read in place
    cache_len=None,                         # scalar or (B,): valid entries
                                            # (>= prefix; per-row = ragged
                                            # continuous-batching rows)
    prefix_lens: Optional[jnp.ndarray] = None,  # (B,) real prefix lengths
                                            # (<= prefix_len); bucket pad
                                            # [real, prefix_len) is masked
    collect_mass: bool = False,
    backend: str = "reference",             # decode-step attention impl:
                                            # "reference" (masked dense) or
                                            # "pallas" (fused ragged kernel)
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray], Optional[jnp.ndarray]]:
    """Returns (out, (new_cache_k, new_cache_v) or (k, v), mass); with
    ``cache_layer`` the new caches are the whole updated stacks."""
    B, S, _ = x.shape
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    with jax.named_scope("projections"):
        q = _proj(p, x, "q", cfg, Hq, Dh)
        k = _proj(p, x, "k", cfg, Hkv, Dh)
        v = _proj(p, x, "v", cfg, Hkv, Dh)

    if mode == "train":
        pos = pos_shift + jnp.arange(S)
        if use_rope:
            pb = jnp.broadcast_to(pos[None], (B, S))
            q = rope(q, pb, cfg.rope_theta)
            k = rope(k, pb, cfg.rope_theta)
        out, mass = _core(cfg)(
            q, k, v, q_pos=pos, kv_pos=pos, causal=causal, window=window)
        return out.reshape(B, S, -1) @ p["wo"], (k, v), mass

    # ---- cached: prefill (S>1) or decode (S==1) ----
    # Ragged rows (continuous batching): cache_len / pos_shift may carry a
    # batch axis and prefix_lens gives each row's REAL prefix length inside
    # the shared bucket. Scalar everything restores the classic uniform
    # path unchanged.
    ragged = (jnp.ndim(cache_len) > 0 or jnp.ndim(pos_shift) > 0
              or prefix_lens is not None)
    self_idx = cache_len - prefix_len                    # index of x[0]
    if ragged:
        base = jnp.broadcast_to(jnp.asarray(pos_shift + self_idx), (B,))
        q_pos = base[:, None] + jnp.arange(S)[None]      # (B, S)
    else:
        q_pos = pos_shift + self_idx + jnp.arange(S)
    if use_rope:
        pb = q_pos if q_pos.ndim == 2 else jnp.broadcast_to(q_pos[None],
                                                            (B, S))
        q = rope(q, pb, cfg.rope_theta)
        k = rope(k, pb, cfg.rope_theta)

    stacked = cache_layer is not None
    Smax = cache_k.shape[-3]
    ring = (cfg.ring_cache and window is not None and Smax == window
            and prefix_len == 0 and not ragged and not stacked)
    if ring:
        # vLLM-style ring buffer: slot for absolute index i is i % W.
        W = Smax
        if S == 1:
            slot = jax.lax.rem(cache_len, W)
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache_k, k.astype(cache_k.dtype), slot, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache_v, v.astype(cache_v.dtype), slot, axis=1)
        else:
            # prefill: attend over the FULL incoming sequence (early query
            # rows need positions the ring will evict), then store only the
            # last W entries for future decode steps.
            out, mass = _core(cfg)(
                q, k, v, q_pos=q_pos, kv_pos=q_pos, causal=causal,
                window=window, mass_mask=None)
            kw = k[:, -W:, :, :] if S >= W else k
            vw = v[:, -W:, :, :] if S >= W else v
            n_w = kw.shape[1]
            pos_w = self_idx + jnp.arange(S - n_w, S)
            slots = jnp.mod(pos_w, W)
            ck = cache_k.at[:, slots].set(kw.astype(cache_k.dtype))
            cv = cache_v.at[:, slots].set(vw.astype(cache_v.dtype))
            return out.reshape(B, S, -1) @ p["wo"], (ck, cv), mass
        cur_last = self_idx + S - 1                  # newest absolute index
        idx = jnp.arange(W)
        # absolute index stored in slot s: largest p <= cur_last, p%W == s
        # (floor-mod so empty slots map to negative positions -> invalid)
        kv_pos_abs = cur_last - jnp.mod(cur_last - idx, W)
        valid = kv_pos_abs >= 0
        out, mass = _core(cfg)(
            q, ck, cv, q_pos=q_pos, kv_pos=pos_shift + kv_pos_abs,
            kv_valid=valid, causal=causal, window=window, mass_mask=None)
        return out.reshape(B, S, -1) @ p["wo"], (ck, cv), mass

    with jax.named_scope("slot_update"):
        ck = _cache_write(cache_k, k, cache_len, cache_layer, ragged)
        cv = _cache_write(cache_v, v, cache_len, cache_layer, ragged)

    if backend == "pallas" and S == 1 and window is None and not collect_mass:
        # Fused ragged decode: one two-segment kernel per layer, no dense
        # (B, Smax) mask materialization. Positions are already baked in
        # (RoPE applied above), so only the validity geometry ships:
        # kv_len = total valid entries, pfx = real prefix entries (0 when
        # ctx_valid masks the prefix at an unselected layer).
        from repro.core.protocol import TRACE_COUNTS
        from repro.kernels.ragged_decode import ragged_decode_stack
        kvl = (jnp.broadcast_to(cache_len, (B,)) + S).astype(jnp.int32)
        if prefix_len:
            pfx = (prefix_lens if prefix_lens is not None
                   else jnp.full((B,), prefix_len, jnp.int32))
            if ctx_valid is not None:
                pfx = jnp.where(ctx_valid, pfx, 0)
        else:
            pfx = None
        # trace-time record of how the cache reached the kernel: the run's
        # stack in place, or a layer the scan sliced out of it
        TRACE_COUNTS["ragged_decode[in_place]" if stacked
                     else "ragged_decode[copy]"] += 1
        sk, sv, layer = (ck, cv, cache_layer) if stacked \
            else (ck[None], cv[None], 0)
        o = ragged_decode_stack(q[:, 0], sk, sv, layer, kvl, pfx,
                                prefix_len=prefix_len)
        with jax.named_scope("projections"):
            o = o.reshape(B, S, -1) @ p["wo"]
        return o, (ck, cv), None

    idx = jnp.arange(Smax)
    shift2 = (jnp.broadcast_to(pos_shift, (B,))[:, None]
              if ragged else None)                       # (B, 1)
    if prefix_len:
        kv_pos = (jnp.where(idx[None] < prefix_len, idx[None],
                            shift2 + (idx[None] - prefix_len))
                  if ragged else
                  jnp.where(idx < prefix_len, idx,
                            pos_shift + (idx - prefix_len)))
    else:
        kv_pos = (shift2 + idx[None]) if ragged else pos_shift + idx
    if ragged:
        valid = idx[None] < (jnp.broadcast_to(cache_len, (B,)) + S)[:, None]
        if prefix_len and prefix_lens is not None:
            # bucket pad [real, prefix_len) never holds sender KV
            valid = valid & ~((idx[None] >= prefix_lens[:, None])
                              & (idx[None] < prefix_len))
    else:
        valid = idx < cache_len + S
    if prefix_len and ctx_valid is not None:
        cvm = jnp.where(idx < prefix_len, ctx_valid, True)
        valid = valid & (cvm[None] if ragged else cvm)
    mass_mask = ((idx < prefix_len) if (collect_mass and prefix_len)
                 else None)
    # decode (S == 1): every valid slot precedes the query by construction
    # (self entries sit at kv_pos <= q_pos; prefix entries are either below
    # the shifted query position or masked by ctx_valid), so the causal
    # comparison over the whole buffer is dead work in the per-token step
    out, mass = _core(cfg)(
        q, layer_of(ck, cache_layer), layer_of(cv, cache_layer), q_pos=q_pos,
        kv_pos=kv_pos, kv_valid=valid, causal=causal and S > 1,
        window=window, mass_mask=mass_mask)
    return out.reshape(B, S, -1) @ p["wo"], (ck, cv), mass


def layer_of(cache, layer):
    """One layer's view of a cache: the layer of a stack, or the cache
    itself where there is no stack (``layer is None``)."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)


def _cache_write(cache, x, cache_len, layer, ragged):
    """Write the S new rows ``x`` (B, S, Hkv, Dh) at each row's length.

    Ragged rows append at their own lengths (clamped so that a dead slot
    rewrites its own masked position rather than walking off the buffer).
    With a ``layer``, ``cache`` is the whole stack and only the new rows
    are written, in place; the rest of the stack is never copied."""
    B, S = x.shape[:2]
    x = x.astype(cache.dtype)
    if not ragged:
        if layer is None:
            return jax.lax.dynamic_update_slice_in_dim(cache, x, cache_len,
                                                       axis=1)
        return jax.lax.dynamic_update_slice(
            cache, x[None], (layer, 0, cache_len, 0, 0))
    start = jnp.minimum(jnp.broadcast_to(cache_len, (B,)),
                        cache.shape[-3] - S)
    if layer is None:
        return jax.vmap(lambda c, r, s: jax.lax.dynamic_update_slice_in_dim(
            c, r, s, axis=0))(cache, x, start)
    if S == 1:
        # decode: one row per slot, all of them in one scatter
        return cache.at[layer, jnp.arange(B), start].set(
            x[:, 0], indices_are_sorted=True, unique_indices=True,
            mode="promise_in_bounds")
    # prefill: a row's S new entries are contiguous, one update each (a
    # scatter would write them one entry at a time)
    for b in range(B):
        cache = jax.lax.dynamic_update_slice(
            cache, x[b][None, None], (layer, b, start[b], 0, 0))
    return cache


def init_cross_attn(key, cfg):
    return init_attn(key, cfg)


def cross_attention(p, cfg, x, enc_k, enc_v):
    """Whisper-style cross attention over precomputed encoder KV."""
    B, S, _ = x.shape
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = _proj(p, x, "q", cfg, Hq, Dh)
    Senc = enc_k.shape[1]
    out, _ = attention_core(
        q, enc_k, enc_v,
        q_pos=jnp.zeros((S,), jnp.int32),
        kv_pos=jnp.zeros((Senc,), jnp.int32),
        causal=False, window=None)
    return out.reshape(B, S, -1) @ p["wo"]


def cross_kv(p, cfg, enc_out):
    """Per-layer cross KV from encoder output: (B, Senc, Hkv, Dh) each."""
    Hkv, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    return (_proj(p, enc_out, "k", cfg, Hkv, Dh),
            _proj(p, enc_out, "v", cfg, Hkv, Dh))
