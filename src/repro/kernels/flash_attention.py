"""Blocked flash attention with fused KVComm context-mass (Pallas / TPU).

This is the receiver's hot loop: attention over ``[sender prefix | self]``
KV with causal masking on the self segment, optional sliding window, GQA, and
— the TPU-native rethink of the paper's Eq. (1) — a *fused* accumulator for
the attention mass each query row assigns to the sender's context tokens.
The paper measures that mass by materializing S×S attention matrices through
HF's ``output_attentions``; here it rides along with the standard
flash-attention running-max rescale at zero extra memory traffic.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — kv innermost so the
(m, l, acc, mass) scratch carries across kv blocks (TPU grids iterate
sequentially, last axis fastest). Block shapes are explicit VMEM BlockSpecs;
the MXU-facing matmuls are (blk_q, d) x (d, blk_k) with d padded to a
multiple of 128 by the wrapper in ``ops.py``.
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


def _flash_kernel(
    q_ref, k_ref, v_ref,            # (1,1,blk_q,d), (1,1,blk_k,d) views
    o_ref,                          # (1,1,blk_q,d)
    mass_ref,                       # (1,1,blk_q,1) or absent
    acc_ref, m_ref, l_ref, ms_ref,  # VMEM scratch
    *,
    blk_q: int,
    blk_k: int,
    seq_q: int,
    seq_kv: int,
    context_len: int,
    q_offset: int,
    causal: bool,
    window: Optional[int],
    collect_mass: bool,
    scale: float,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        if collect_mass:
            ms_ref[...] = jnp.zeros_like(ms_ref)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # absolute positions of this tile
    rq = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    rk = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    q_pos = q_offset + rq
    in_ctx = rk < context_len
    kv_pos = jnp.where(in_ctx, rk, q_offset + (rk - context_len))
    allow = (rq < seq_q) & (rk < seq_kv)
    if causal:
        allow = allow & (kv_pos <= q_pos)
    if window is not None:
        allow = allow & ((q_pos - kv_pos) < window)
    s = jnp.where(allow, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)[:, None]                 # (blk_q, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(allow, p, 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=1)[:, None]

    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    if collect_mass:
        pm = jnp.where(in_ctx, p, 0.0)
        ms_ref[...] = ms_ref[...] * alpha + jnp.sum(pm, axis=1)[:, None]
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if collect_mass:
            mass_ref[0, 0] = (ms_ref[...] / l).astype(mass_ref.dtype)


def flash_attention_bhsd(
    q: jnp.ndarray,                 # (B, Hq, Sq, D)
    k: jnp.ndarray,                 # (B, Hkv, Skv, D)
    v: jnp.ndarray,
    *,
    context_len: int = 0,
    q_offset: int = 0,
    causal: bool = True,
    window: Optional[int] = None,
    collect_mass: bool = False,
    blk_q: int = 128,
    blk_k: int = 128,
    scale: Optional[float] = None,
    interpret: bool = False,
):
    """Core pallas call on (B, H, S, D) layout. Sq/Skv must be multiples of
    the block sizes (``ops.py`` pads). Returns (out, mass|None) where mass is
    the per-row context attention mass, shape (B, Hq, Sq), already normalized
    by each row's softmax denominator (i.e. true probability mass)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    assert Sq % blk_q == 0 and Skv % blk_k == 0
    nq = Sq // blk_q
    nk = Skv // blk_k
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, blk_q=blk_q, blk_k=blk_k, seq_q=Sq, seq_kv=Skv,
        context_len=context_len, q_offset=q_offset, causal=causal,
        window=window, collect_mass=collect_mass, scale=scale)
    if not collect_mass:  # drop the mass_ref positional slot
        base = kernel
        kernel = lambda qr, kr, vr, orf, acc, m, l, ms: base(
            qr, kr, vr, orf, None, acc, m, l, ms)

    out_shape = [jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, blk_q, D),
                              lambda b, h, iq, ik: (b, h, iq, 0))]
    if collect_mass:
        out_shape.append(jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, blk_q, 1),
                                      lambda b, h, iq, ik: (b, h, iq, 0)))

    res = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)

    if collect_mass:
        out, mass = res
        return out, mass[..., 0]
    return res[0], None
