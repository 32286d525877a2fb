"""Jit'd public wrappers around the Pallas kernels.

These handle layout (B,S,H,D) <-> (B,H,S,D), padding to block multiples, and
the interpret-mode switch: ``interpret=None`` compiles the kernel with Mosaic
on a TPU backend and runs the Pallas interpreter everywhere else.
The pure-jnp oracles live in ``ref.py``; ``tests/test_kernels.py`` sweeps
shapes and dtypes asserting allclose between the two.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.flash_decode import flash_decode, flash_decode_partials
# re-export: the serving hot loop's two-segment packed-prefix decode
# (reads the cache in its own layout — see kernels/ragged_decode.py)
from repro.kernels.ragged_decode import ragged_decode  # noqa: F401
from repro.kernels.rwkv_scan import wkv6


def _interpret(flag: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if flag is None else flag


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(
    jax.jit,
    static_argnames=("context_len", "q_offset", "causal", "window",
                     "collect_mass", "blk_q", "blk_k", "interpret"))
def flash_attention(
    q, k, v, *,
    context_len: int = 0,
    q_offset: int = 0,
    causal: bool = True,
    window: Optional[int] = None,
    collect_mass: bool = False,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """(B, S, H, D)-layout flash attention with KVComm prefix semantics.

    kv rows [0, context_len) are the sender prefix at absolute positions
    [0, context_len); self rows sit at q_offset + j. Returns (out, mass)
    with mass (B,) — Eq. (1) averaged over heads and query rows — or None.
    """
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    import math
    scale = 1.0 / math.sqrt(D)
    qb = jnp.moveaxis(q, 1, 2)
    kb = jnp.moveaxis(k, 1, 2)
    vb = jnp.moveaxis(v, 1, 2)
    blk_q = min(blk_q, max(8, 1 << (Sq - 1).bit_length()))
    blk_k = min(blk_k, max(8, 1 << (Skv - 1).bit_length()))
    qb, _ = _pad_to(qb, 2, blk_q)
    kb, _ = _pad_to(kb, 2, blk_k)
    vb, _ = _pad_to(vb, 2, blk_k)
    dpad = (-D) % 128
    if dpad:
        qb = jnp.pad(qb, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        kb = jnp.pad(kb, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        vb = jnp.pad(vb, ((0, 0), (0, 0), (0, 0), (0, dpad)))
    out, mass = flash_attention_bhsd(
        qb, kb, vb, context_len=context_len, q_offset=q_offset,
        causal=causal, window=window, collect_mass=collect_mass,
        blk_q=blk_q, blk_k=blk_k, scale=scale,
        interpret=_interpret(interpret))
    out = jnp.moveaxis(out[:, :, :Sq, :D], 1, 2)
    if mass is not None:
        mass = jnp.mean(mass[:, :, :Sq], axis=(1, 2))
    return out, mass


@functools.partial(
    jax.jit, static_argnames=("window", "blk_k", "interpret"))
def decode_attention(q, k, v, kv_len, *, window=None, blk_k=256,
                     interpret: Optional[bool] = None):
    """One-token decode over a long cache. q: (B, Hq, D); k/v (B, S, Hkv, D).
    Pads S to the kv block size; padding is masked by kv_len."""
    S = k.shape[1]
    blk_k = min(blk_k, max(8, 1 << (S - 1).bit_length()))
    k, _ = _pad_to(k, 1, blk_k)
    v, _ = _pad_to(v, 1, blk_k)
    D = q.shape[-1]
    dpad = (-D) % 128
    import math
    scale = 1.0 / math.sqrt(D)
    if dpad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, dpad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dpad)))
    out = flash_decode(q, k, v, kv_len, window=window, blk_k=blk_k,
                       scale=scale, interpret=_interpret(interpret))
    return out[..., :D]


@functools.partial(
    jax.jit, static_argnames=("window", "blk_k", "interpret"))
def decode_attention_partials(q, k, v, kv_len, *, window=None, blk_k=256,
                              interpret: Optional[bool] = None):
    """Shard-local flash-decode partials (o, m, l) for the sequence-parallel
    combine (``ref.combine_decode_partials``)."""
    S = k.shape[1]
    blk_k = min(blk_k, max(8, 1 << (S - 1).bit_length()))
    k, _ = _pad_to(k, 1, blk_k)
    v, _ = _pad_to(v, 1, blk_k)
    D = q.shape[-1]
    import math
    scale = 1.0 / math.sqrt(D)
    dpad = (-D) % 128
    if dpad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, dpad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dpad)))
    o, m, l = flash_decode_partials(q, k, v, kv_len, window=window,
                                    blk_k=blk_k, scale=scale,
                                    interpret=_interpret(interpret))
    return o[..., :D], m, l


@functools.partial(jax.jit, static_argnames=("blk_t", "interpret"))
def wkv6_scan(r, k, v, w, u, state, *, blk_t: int = 32,
              interpret: Optional[bool] = None):
    """Chunked RWKV6 recurrence; layout (B, S, H, hd) like the oracle."""
    return wkv6(r, k, v, w, u, state, blk_t=blk_t,
                interpret=_interpret(interpret))


__all__ = ["flash_attention", "decode_attention",
           "decode_attention_partials", "wkv6_scan", "ref"]
