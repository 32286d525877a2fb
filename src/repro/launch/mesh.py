"""Production mesh definitions (TPU v5e target).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the "pod"
axis extends data parallelism across the inter-pod links (DCN-ish); tensor
parallelism never crosses pods.

Functions, not module-level constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Auto (compiler-propagated) axes: the dry-run pins activations with
    ``distributed.hints`` constraints, which an Explicit mesh would treat
    as assertions instead."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host actually has — a 1x1 mesh on the CPU container."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def mesh_axes(mesh) -> tuple:
    """Returns (dp_axes, tp_axis): dp_axes is 'data' or ('pod','data')."""
    names = mesh.axis_names
    if "pod" in names:
        return ("pod", "data"), "model"
    return "data", "model"


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
