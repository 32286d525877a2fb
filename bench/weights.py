"""Seeded random weights of a configuration, made on the device in one jit.

The tree is the one the configuration's family gives (``shapes``), in the
layout the program serves, in the configuration's serving dtype.  By
default (``leaf``) RMSNorm gains are drawn as ``1 + 0.1 N(0, 1)``; the
program stores a gain as its offset from 1, so the tree holds the offsets
and the reference reads them back as ``1 + offset``.  A family may draw
its leaves by a rule of its own.  Nothing here imports the program: the
benchmark owns the weights, and the reference reads the same arrays.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import cells


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def leaf(key, path, shape, dtype):
    """One leaf at ``path`` (a tree path) drawn from ``key``."""
    name = path[-1].key
    if name in ("ln1", "ln2", "final_norm"):
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        x = jax.random.normal(key, shape, jnp.float32)
    else:   # a projection: unit-variance outputs for unit-variance inputs
        x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnames=("tree_shapes", "dtype", "draw"))
def _make(seed, tree_shapes, dtype, draw):
    tree = jax.tree_util.tree_unflatten(tree_shapes[0], tree_shapes[1])
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]
    keys = jax.random.split(jax.random.key(seed), len(paths))
    leaves = [draw(k, p, s, dtype) for k, (p, s) in zip(keys, paths)]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree, is_leaf=_is_shape), leaves)


def make(conf: dict, seed: int):
    """The parameter tree of ``conf`` drawn from ``seed``, on the default
    device, in the configuration's serving dtype."""
    fam = cells.family(conf)
    leaves, treedef = jax.tree_util.tree_flatten(fam.shapes(conf),
                                                 is_leaf=_is_shape)
    return _make(jnp.uint32(seed), (treedef, tuple(leaves)),
                 jnp.dtype(conf["torch_dtype"]), getattr(fam, "leaf", leaf))
