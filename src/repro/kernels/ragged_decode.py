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

The kernel reads the cache where it lives: a run's whole ``(m, B, S, Hkv,
d)`` layer stack, viewed as ``(m, B, S * Hkv, d)`` (on the TPU a bitcast
while ``Hkv`` is a multiple of the 8-row tile), with the layer index a
scalar-prefetch operand of the index maps.  The grid is (batch, kv blocks),
kv innermost; each block holds ``blk_k`` tokens of every KV head, and the
kernel walks the heads in VMEM, head ``h`` being every ``Hkv``-th row.  Per
head the arithmetic is the flash-decode online softmax with (acc, m, l)
carried across blocks and all G query heads of the group in one pass.  The
KV axis is not padded: the tail block reads past the stack, and its rows
there are masked out of V as well as out of the scores.  Fully-masked rows
(dead slots, ``kv_len == 0``) emit defined zeros.
``kernels/ref.ragged_decode_reference`` is the oracle.
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

# rows (tokens x KV heads) of K per block when the caller names no blk_k:
# 8,192 rows of d = 128 bf16 are 2 MiB, which keeps the per-block DMA long
# against the grid step's fixed cost at 8 or 32 KV heads alike
_BLOCK_ROWS = 8192


def segment_mask(rk, kv_len, pfx, *, prefix_len: int, seq_kv: int):
    """The two-segment validity mask of cache slots ``rk`` for one row:
    ``rk < prefix_len ? rk < pfx : rk < kv_len``, and never past the real
    KV length ``seq_kv``.  It selects the row's integer LIMIT and compares
    once — Mosaic lowers an int32 select, but not a select between two
    bool vectors."""
    limit = jnp.where(rk < prefix_len, pfx, kv_len) if prefix_len > 0 \
        else kv_len
    return (rk < limit) & (rk < seq_kv)


def _head_groups(hkv: int, dtype):
    """The KV heads a kernel step loads together: pairs for bf16 (one 32-bit
    row holds a token's rows of heads 2c and 2c+1), else one at a time."""
    n = 2 if jnp.dtype(dtype) == jnp.bfloat16 and hkv % 2 == 0 else 1
    return [tuple(range(c, c + n)) for c in range(0, hkv, n)]


def _load_heads(ref, heads, hkv: int, blk_k: int):
    """The (blk_k, d) float32 rows of each of ``heads`` in a (1, 1, blk_k *
    hkv, d) block, where token t's head h is row ``t * hkv + h``.  Mosaic
    strides 32-bit rows only, so a bf16 block is read as uint32 rows, each
    the pair (2c, 2c+1) of one token: the even head in the low half.  A
    bf16 is the top half of its float32, so a shift widens it exactly."""
    if len(heads) == 1:
        rows = pl.ds(heads[0], blk_k, stride=hkv)
        return [ref[0, 0, rows, :].astype(jnp.float32)]
    words = ref.reshape(blk_k * hkv, ref.shape[-1]).bitcast(jnp.uint32)
    w = words[pl.ds(heads[0] // 2, blk_k, stride=hkv // 2), :]
    return [pltpu.bitcast(w << 16, jnp.float32),
            pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)]


def _ragged_decode_kernel(
    layer_ref,                      # (1,) int32 SMEM — read by index maps
    len_ref,                        # (B,) int32 SMEM — total valid entries
    pfx_ref,                        # (B,) int32 SMEM — real prefix entries
    q_ref,                          # (1, Hq, d) float32
    k_ref, v_ref,                   # (1, 1, blk_k * Hkv, d)
    o_ref,                          # (1, Hq, d) float32
    acc_ref, m_ref, l_ref,          # scratch (Hq, d), (Hq, 1), (Hq, 1)
    *,
    blk_k: int,
    hkv: int,
    seq_kv: int,
    prefix_len: int,
    scale: float,
):
    del layer_ref
    b = pl.program_id(0)
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    G = q_ref.shape[1] // hkv
    rk = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (G, blk_k), 1)
    allow = segment_mask(rk, len_ref[b], pfx_ref[b], prefix_len=prefix_len,
                         seq_kv=seq_kv)
    # past the stack's end the tail block holds whatever the buffer held,
    # NaN included: p is 0 there, but 0 x NaN is not, so V's rows go too
    tail = seq_kv % blk_k != 0
    if tail:
        inside = (ik * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_k, 1), 0)) < seq_kv
    for heads in _head_groups(hkv, k_ref.dtype):
        ks = _load_heads(k_ref, heads, hkv, blk_k)
        vs = _load_heads(v_ref, heads, hkv, blk_k)
        for h, k, v in zip(heads, ks, vs):
            g = slice(h * G, (h + 1) * G)
            q = q_ref[0, g, :]                                   # (G, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(allow, s, NEG_INF)
            m_prev = m_ref[g, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(allow, jnp.exp(s - m_new), 0.0)
            l_ref[g, :] = l_ref[g, :] * alpha + jnp.sum(p, axis=1)[:, None]
            if tail:
                v = jnp.where(inside, v, 0.0)
            acc_ref[g, :] = acc_ref[g, :] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g, :] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        # dead slots (kv_len == 0 and no real prefix) mask everything:
        # l == 0 there, and the row must come out as defined zeros
        l = l_ref[...]
        o_ref[0] = jnp.where(l > 0.0, acc_ref[...] / jnp.maximum(l, 1e-30),
                             0.0)


def ragged_decode_stack(q, k, v, layer, kv_len, prefix_lens=None, *,
                        prefix_len: int = 0, blk_k: Optional[int] = None,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """Fused one-token ragged decode of layer ``layer`` of a cache stack.

    q: (B, Hq, d); k/v: (m, B, Skv, Hkv, d) — a layer run's whole stack, read
    in place, never sliced or copied; ``layer`` a traced or static index
    into its first axis. Each row is laid out
    ``[prefix bucket (prefix_len) | self | pad]``. ``kv_len`` (B,) counts ALL
    valid entries (prefix bucket + self); ``prefix_lens`` (B,) counts the
    real entries inside the bucket (entries in ``[prefix_lens[b],
    prefix_len)`` are bucket padding and are masked out).
    ``prefix_len == 0`` (the prefix-free / unselected-layer case) needs no
    ``prefix_lens``. ``blk_k`` keys per block; by default as many as keep a
    block at ``_BLOCK_ROWS`` rows. Returns (B, Hq, d) in q.dtype.
    """
    B, Hq, D = q.shape
    m, _, Skv, Hkv, _ = k.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if blk_k is None:
        blk_k = max(16, _BLOCK_ROWS // Hkv)
    blk_k = max(1, min(blk_k, Skv))
    nk = pl.cdiv(Skv, blk_k)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    if prefix_lens is None:
        prefix_lens = jnp.full((B,), prefix_len, jnp.int32)
    prefix_lens = jnp.broadcast_to(jnp.asarray(prefix_lens, jnp.int32), (B,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    kernel = functools.partial(
        _ragged_decode_kernel, blk_k=blk_k, hkv=Hkv, seq_kv=Skv,
        prefix_len=prefix_len, scale=scale)
    rows = blk_k * Hkv
    kv_spec = pl.BlockSpec((1, 1, rows, D),
                           lambda b, ik, lay, *_: (lay[0], b, ik, 0))
    q_spec = pl.BlockSpec((1, Hq, D), lambda b, ik, *_: (b, 0, 0))
    # the per-row lengths and the layer ride in SMEM via scalar prefetch;
    # index maps receive the prefetched refs as trailing arguments
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
        ],
    )
    # K and V blocks, double-buffered, plus room for the f32 head slices
    block_bytes = rows * D * k.dtype.itemsize
    vmem = 4 * block_bytes + 16 * 2**20
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ragged_decode",
    )(layer, kv_len, prefix_lens, q.astype(jnp.float32),
      k.reshape(m, B, Skv * Hkv, D), v.reshape(m, B, Skv * Hkv, D))
    return out.astype(q.dtype)


def ragged_decode(q, k, v, kv_len, prefix_lens=None, **kw):
    """``ragged_decode_stack`` over one layer's cache: k/v (B, Skv, Hkv, d)."""
    return ragged_decode_stack(q, k[None], v[None], 0, kv_len, prefix_lens,
                               **kw)
