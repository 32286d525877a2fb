"""Activation-sharding hints for the model code.

GSPMD alone resolves the FSDP-weight (dim over 'data') vs batch-activation
(also over 'data') conflict badly on some backends: it un-shards the batch of
remat-saved residuals and of the logits matmul instead of all-gathering
weights just-in-time (measured: 171 GB/device saved residuals for
qwen1.5-110B train_4k — EXPERIMENTS.md §Perf iteration 2). These explicit
``with_sharding_constraint`` hints pin activations to
``P(data_axes, 'model', None)`` — batch over data, sequence over model
(Megatron-style sequence parallelism between blocks; pointwise norms are
seq-local so this is free) — which forces the intended ZeRO-3 behaviour.

The hints are a no-op unless a launcher installs the mesh axes via
``set_axes`` (tests and CPU serving never see them); axis sizes come from
the mesh the launcher made current with ``jax.set_mesh``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

_AXES: Optional[Tuple] = None   # (dp_axes, tp_axis)


def set_axes(dp, tp) -> None:
    global _AXES
    _AXES = (dp, tp)


def clear() -> None:
    global _AXES
    _AXES = None


def shard_activations(x):
    """Constrain (B, S, d) activations: batch->data, seq->model."""
    if _AXES is None or x.ndim != 3:
        return x
    dp, tp = _AXES
    spec = [None, None, None]
    if x.shape[0] % _size(dp) == 0:
        spec[0] = dp
    if x.shape[1] % _size(tp) == 0 and x.shape[1] > 1:
        spec[1] = tp
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_logits(x):
    """Constrain (B, S, V) logits: batch->data, vocab->model."""
    if _AXES is None or x.ndim != 3:
        return x
    dp, tp = _AXES
    spec = [dp if x.shape[0] % _size(dp) == 0 else None, None,
            tp if x.shape[2] % _size(tp) == 0 else None]
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _size(axis) -> int:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1
    names = axis if isinstance(axis, (tuple, list)) else (axis,)
    return math.prod(mesh.shape[a] for a in names)
