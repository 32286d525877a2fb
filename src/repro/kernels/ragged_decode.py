"""Ragged decode over the two-segment packed prefix layout (Pallas).

The serving hot loop. A scheduler slot's cache row is laid out as

    [ shared prefix bucket (prefix_len slots) | self tokens | pad ]

where only ``prefix_lens[b] <= prefix_len`` prefix entries are real (the
bucket is padded to a static size so jit specializes per geometry, not per
request) and the per-row valid total is ``kv_len[b]`` (prefix bucket + self
count). Unselected layers run prefix-free (``prefix_len == 0``) under the
packed fast path, or with ``prefix_lens`` forced to 0 by ``ctx_valid`` under
the dense fallback — either way the same kernel serves both segments with a
single per-row mask:

    allow[j] = (j <  prefix_len) ? j < prefix_lens[b]   # real prefix only
             : (j <  kv_len[b])                         # self tokens

RoPE is applied to q and the cache before the kernel (positions, including
``pos_shift``, are already baked in), so the kernel is position-free.

Grid and scratch mirror ``flash_decode``: (batch, kv_heads, kv_blocks) with
kv innermost and (acc, m, l) carried across blocks; one invocation handles
all G query heads of a KV-head group. Fully-masked rows (dead slots,
``kv_len == 0``) emit defined zeros. The KV axis is padded internally to a
block multiple and ``blk_k`` is clamped for short caches — any slot-table
geometry is legal. ``kernels/ref.ragged_decode_reference`` is the oracle.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# TPU lane width; the head dim is padded up to this off-TPU too so compiled
# and interpreted runs share one code path
_LANE = 128


def segment_mask(rk, kv_len, pfx, *, prefix_len: int, seq_kv: int):
    """The two-segment validity mask of cache slots ``rk`` for one row:
    ``rk < prefix_len ? rk < pfx : rk < kv_len``, and never past the real
    KV length ``seq_kv``.  It selects the row's integer LIMIT and compares
    once — Mosaic lowers an int32 select, but not a select between two
    bool vectors."""
    limit = jnp.where(rk < prefix_len, pfx, kv_len) if prefix_len > 0 \
        else kv_len
    return (rk < limit) & (rk < seq_kv)


def _ragged_decode_kernel(
    len_ref,                        # (B,) int32 SMEM — total valid entries
    pfx_ref,                        # (B,) int32 SMEM — real prefix entries
    q_ref,                          # (1, 1, G, d)
    k_ref, v_ref,                   # (1, 1, blk_k, d)
    o_ref,                          # (1, 1, G, d)
    acc_ref, m_ref, l_ref,          # scratch
    *,
    blk_k: int,
    seq_kv: int,
    prefix_len: int,
    scale: float,
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
    allow = segment_mask(rk, kv_len, pfx_ref[b], prefix_len=prefix_len,
                         seq_kv=seq_kv)
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
        # dead slots (kv_len == 0 and no real prefix) mask everything:
        # l == 0 there, and the row must come out as defined zeros
        l = l_ref[...]
        o_ref[0, 0] = jnp.where(
            l > 0.0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0
        ).astype(o_ref.dtype)


def _call(q, k, v, kv_len, prefix_lens, *, prefix_len, blk_k, scale,
          interpret):
    """q: (B, Hkv, G, d); k/v: (B, Hkv, Skv, d); kv_len/prefix_lens: (B,)."""
    B, Hkv, G, D = q.shape
    Skv = k.shape[2]
    blk_k = max(1, min(blk_k, Skv))
    pad = (-Skv) % blk_k
    if pad:
        # tail blocks are masked by rk < seq_kv (seq_kv stays the REAL
        # length), so zero-padding the block axis is purely structural
        with jax.named_scope("cache_copy"):
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (Skv + pad) // blk_k
    kernel = functools.partial(
        _ragged_decode_kernel, blk_k=blk_k, seq_kv=Skv,
        prefix_len=prefix_len, scale=scale)
    # the per-row lengths ride in SMEM via scalar prefetch (a (1, 1) VMEM
    # block of a (B, 1) array violates Mosaic's (8, 128) tiling rule);
    # index maps receive the prefetched refs as trailing arguments
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, ik, *_: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, ik, *_: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, ik, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        name="ragged_decode",
    )(kv_len.astype(jnp.int32), prefix_lens.astype(jnp.int32), q, k, v)


def ragged_decode(q, k, v, kv_len, prefix_lens=None, *, prefix_len: int = 0,
                  blk_k: int = 256, scale: Optional[float] = None,
                  interpret: Optional[bool] = None):
    """Fused one-token ragged decode over a two-segment cache row.

    q: (B, Hq, d); k/v: (B, Skv, Hkv, d) with the layout
    ``[prefix bucket (prefix_len) | self | pad]`` per row. ``kv_len`` (B,)
    counts ALL valid entries (prefix bucket + self); ``prefix_lens`` (B,)
    counts the real entries inside the bucket (entries in
    ``[prefix_lens[b], prefix_len)`` are bucket padding and are masked out).
    ``prefix_len == 0`` (the prefix-free / unselected-layer case) needs no
    ``prefix_lens``. Returns (B, Hq, d) in q.dtype.
    """
    B, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # the kernel's layout of the cache, a copy of every row
    with jax.named_scope("cache_copy"):
        if D % _LANE:
            dpad = _LANE - D % _LANE
            q = jnp.pad(q, ((0, 0), (0, 0), (0, dpad)))
            k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dpad)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        qh = q.reshape(B, Hkv, G, q.shape[-1])
        kb = jnp.moveaxis(k, 1, 2)   # (B, Hkv, Skv, d)
        vb = jnp.moveaxis(v, 1, 2)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    if prefix_lens is None:
        prefix_lens = jnp.full((B,), prefix_len, jnp.int32)
    prefix_lens = jnp.broadcast_to(jnp.asarray(prefix_lens, jnp.int32), (B,))
    out = _call(qh, kb, vb, kv_len, prefix_lens, prefix_len=prefix_len,
                blk_k=blk_k, scale=scale, interpret=interpret)
    return out.reshape(B, Hq, -1)[..., :D]
