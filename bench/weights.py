"""Seeded random weights of a configuration, made on the device in one jit.

The tree has the layout the program serves (``repro.models.transformer``):
one stacked attention run of ``num_layers`` layers, untied head, bfloat16.
RMSNorm gains are drawn as ``1 + 0.1 N(0, 1)``; the program stores a gain
as its offset from 1, so the tree holds the offsets and the reference reads
them back as ``1 + offset``.  Nothing here imports the program: the
benchmark owns the weights, and the reference reads the same arrays.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def shapes(conf: dict) -> dict:
    """Leaf shapes of the parameter tree of a configuration file."""
    d, f, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    L, D = conf["num_hidden_layers"], conf["head_dim"]
    Hq, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    return {
        "embed": (V, d),
        "final_norm": (d,),
        "blocks": [{
            "ln1": (L, d),
            "attn": {"wq": (L, d, Hq * D), "wk": (L, d, Hkv * D),
                     "wv": (L, d, Hkv * D), "wo": (L, Hq * D, d)},
            "ln2": (L, d),
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        }],
        "lm_head": (d, V),
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _leaf(key, path, shape, dtype):
    name = path[-1].key
    if name in ("ln1", "ln2", "final_norm"):
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        x = jax.random.normal(key, shape, jnp.float32)
    else:   # a projection: unit-variance outputs for unit-variance inputs
        x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnames=("tree_shapes", "dtype"))
def _make(seed, tree_shapes, dtype):
    tree = jax.tree_util.tree_unflatten(tree_shapes[0], tree_shapes[1])
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]
    keys = jax.random.split(jax.random.key(seed), len(paths))
    leaves = [_leaf(k, p, s, dtype) for k, (p, s) in zip(keys, paths)]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree, is_leaf=_is_shape), leaves)


def make(conf: dict, seed: int):
    """The parameter tree of ``conf`` drawn from ``seed``, on the default
    device, in the configuration's serving dtype."""
    tree = shapes(conf)
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_shape)
    return _make(jnp.uint32(seed), (treedef, tuple(leaves)),
                 jnp.dtype(conf["torch_dtype"]))
