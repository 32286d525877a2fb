"""The work a served request requires, counted from valid lengths.

These count what the computation needs, whatever implements it: matrix
products of the weights for the tokens that need them, attention over the
keys each query may see, and the bytes a decode-attention kernel has to
move.  Padding, bucket slack, dead slots and masked-out keys are not
counted, so a faster implementation of the same work reads higher.

A multiply-add is 2 FLOPs.  The embedding lookup is free.
"""
from __future__ import annotations

from typing import Sequence

BF16 = 2


def layer_matmul_params(conf: dict) -> int:
    """Weights of one layer's projections and MLP (norm gains excluded)."""
    d, f, D = conf["hidden_size"], conf["intermediate_size"], conf["head_dim"]
    Hq, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    return d * (Hq + 2 * Hkv) * D + Hq * D * d + 3 * d * f


def head_flops(conf: dict) -> int:
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def attn_flops(conf: dict, keys: int) -> int:
    """Scores and weighted values of one query row against ``keys`` keys,
    in one layer."""
    return 4 * conf["num_attention_heads"] * conf["head_dim"] * keys


def sender_prefill_flops(conf: dict, prefix: int) -> int:
    """The sender's pass over ``prefix`` tokens (BOS included), every
    layer, causal; no head (only keys and values leave the sender)."""
    L = conf["num_hidden_layers"]
    causal_keys = prefix * (prefix + 1) // 2
    return L * (2 * layer_matmul_params(conf) * prefix
                + attn_flops(conf, causal_keys))


def receiver_prefill_flops(conf: dict, query: int, prefix: int,
                           selected: int) -> int:
    """The receiver's pass over ``query`` real tokens; ``selected`` layers
    also see ``prefix`` keys; the head runs once, for the first reply
    token."""
    L = conf["num_hidden_layers"]
    causal_keys = query * (query + 1) // 2
    return (L * (2 * layer_matmul_params(conf) * query
                 + attn_flops(conf, causal_keys))
            + selected * attn_flops(conf, query * prefix)
            + head_flops(conf))


def decode_row_flops(conf: dict, own: int, prefix: int,
                     selected: int) -> int:
    """One decode token of one live row that sees ``own`` keys of its own
    (the new token included) and, at ``selected`` layers, ``prefix``."""
    L = conf["num_hidden_layers"]
    return (L * (2 * layer_matmul_params(conf) + attn_flops(conf, own))
            + selected * attn_flops(conf, prefix) + head_flops(conf))


def decode_attn_call(conf: dict, keys: Sequence[int]):
    """(FLOPs, bytes) one decode-attention call requires for live rows that
    see ``keys[i]`` keys each: read q, the valid keys and values, write
    the output, all in bfloat16."""
    Hq, Hkv, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    n = sum(keys)
    flops = 4 * Hq * D * n
    nbytes = BF16 * (2 * Hkv * D * n + 2 * Hq * D * len(keys))
    return flops, nbytes
