"""Flash decode: one query token attending over a long KV cache (Pallas).

The decode_32k / long_500k hot loop. One kernel invocation handles all G
query heads of a KV-head group at once — the (G, d) x (d, blk_k) matmul keeps
the MXU busy even at q_len == 1 (G is 6 for mixtral, 8 for qwen).

Two variants share the kernel body:
  * ``flash_decode``          — returns the normalized attention output.
  * ``flash_decode_partials`` — returns UNNORMALIZED (o, m, l) per shard for
    the sequence-parallel combine (``ref.combine_decode_partials``); this is
    what the distributed long-context path runs under ``shard_map``, so a
    524k-token cache sharded 256-ways never has to be gathered.

Grid: (batch, kv_heads, num_kv_blocks) — kv innermost, (m, l, acc) scratch
carried across blocks. kv_len arrives as a per-batch int32 so ragged caches
(continuous batching) mask correctly.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,                        # (B,) int32 SMEM (scalar prefetch)
    q_ref,                          # (1, 1, G, d)
    k_ref, v_ref,                   # (1, 1, blk_k, d)
    o_ref, m_out_ref, l_out_ref,    # (1,1,G,d), (1,1,G,1), (1,1,G,1)
    acc_ref, m_ref, l_ref,          # scratch
    *,
    blk_k: int,
    seq_kv: int,
    window: Optional[int],
    scale: float,
    normalize: bool,
):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                  # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (blk_k, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    G = s.shape[0]
    rk = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (G, blk_k), 1)
    allow = (rk < kv_len) & (rk < seq_kv)
    if window is not None:
        q_pos = kv_len - 1
        allow = allow & ((q_pos - rk) < window)
    s = jnp.where(allow, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(allow, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)[:, None]
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        if normalize:
            # fully-masked rows (kv_len == 0: dead/empty continuous-batching
            # slots) accumulate l == 0; emit DEFINED zeros for them instead
            # of whatever 0/eps garbage the floor division would produce —
            # freed slots must never perturb anything downstream
            l = l_ref[...]
            o_ref[0, 0] = jnp.where(
                l > 0.0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0
            ).astype(o_ref.dtype)
        else:
            o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def _call(q, k, v, kv_len, *, window, blk_k, scale, normalize, interpret):
    """q: (B, Hkv, G, d); k/v: (B, Hkv, Skv, d); kv_len: (B,) int32."""
    B, Hkv, G, D = q.shape
    Skv = k.shape[2]
    blk_k = max(1, min(blk_k, Skv))
    pad = (-Skv) % blk_k
    if pad:
        # tail blocks stay masked by rk < seq_kv (seq_kv is kept at the REAL
        # length below), so zero-padding the block axis is purely structural —
        # scheduler slot tables need not be block-multiples
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (Skv + pad) // blk_k
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    kernel = functools.partial(
        _decode_kernel, blk_k=blk_k, seq_kv=Skv, window=window, scale=scale,
        normalize=normalize)
    # kv_len rides in SMEM via scalar prefetch (see ragged_decode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik, _: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, ik, _: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, ik, _: (b, h, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik, _: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, ik, _: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, ik, _: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D),
                                 jnp.float32 if not normalize else q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(kv_len.astype(jnp.int32), q, k, v)
    return out, m[..., 0], l[..., 0]


def flash_decode(q, k, v, kv_len, *, window=None, blk_k=256, scale=None,
                 interpret=False):
    """q: (B, Hq, d); k/v: (B, Skv, Hkv, d). Returns (B, Hq, d)."""
    B, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qh = q.reshape(B, Hkv, G, D)
    kb = jnp.moveaxis(k, 1, 2)   # (B, Hkv, Skv, d)
    vb = jnp.moveaxis(v, 1, 2)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    out, _, _ = _call(qh, kb, vb, kv_len, window=window, blk_k=blk_k,
                      scale=scale, normalize=True, interpret=interpret)
    return out.reshape(B, Hq, D)


def flash_decode_partials(q, k, v, kv_len, *, window=None, blk_k=256,
                          scale=None, interpret=False
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shard-local partials (o unnormalized, m, l); see ref.py combine."""
    B, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qh = q.reshape(B, Hkv, G, D)
    kb = jnp.moveaxis(k, 1, 2)
    vb = jnp.moveaxis(v, 1, 2)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    o, m, l = _call(qh, kb, vb, kv_len, window=window, blk_k=blk_k,
                    scale=scale, normalize=False, interpret=interpret)
    return o.reshape(B, Hq, D), m.reshape(B, Hq), l.reshape(B, Hq)
