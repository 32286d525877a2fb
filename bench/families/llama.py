"""The Llama family: a Llama-style decoder and the KVComm hop.

The configuration file holds the published ``config.json`` keys of a
dense decoder with RMSNorm, rotary embeddings, grouped-query attention
and a SwiGLU MLP (``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``num_hidden_layers``, ``vocab_size``, ``rope_theta``, ``rms_norm_eps``).

Weights: one stacked attention run of ``num_hidden_layers`` layers with a
dense SwiGLU ``mlp``, untied head, in the layout the program serves.

Counts (``families/__init__.py``): a multiply-add is 2 FLOPs and the
embedding lookup is free; every layer holds every key it may see.

The plain reference, written from the published descriptions, not from
the program: RMSNorm, rotary embeddings (rotate-half, base
``rope_theta``), grouped-query attention with softmax in float32, a
SwiGLU MLP, no biases, an untied head.  The KVComm hop (arXiv:2510.03346,
section 3) is:

* the sender runs ``[BOS] + context`` and keeps every layer's keys
  (rotated at positions ``0 .. P-1``) and values;
* calibration: the receiver runs the calibration query with the sender's
  prefix at every layer; a layer's score is the attention mass on the
  prefix, averaged over heads and query rows, min-max normalized, mixed
  ``alpha * s + (1 - alpha) * prior`` with a Gaussian depth prior
  (``mu = L/2``, ``sigma = 10``, layers counted from 1), and the top
  ``ceil(ratio * L)`` layers are selected;
* the selected layers' keys and values cross an int8 wire: symmetric,
  one scale per layer and tensor, ``scale = max|x| / 127``;
* the receiver runs ``query + reply`` at positions ``P + j``; a selected
  layer attends to the whole prefix and causally to its own tokens, an
  unselected layer to its own tokens only.

Everything is computed in float32 at ``Precision.HIGHEST``, layer by layer
and in blocks of rows so that it fits beside the weights.  ``policy="fp8"``
is the control: the same computation with every matrix product's operands
rounded to float8 e4m3 (weights per output column, activations per row).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BF16 = 2


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the published
    keys as run, plus the program options the file names."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=conf["name"], arch_type="dense", source=conf["source"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"], remat=False, **conf.get("program", {}))


# -- weights -------------------------------------------------------------------
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


# -- counts --------------------------------------------------------------------
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
                           layers: Sequence[int]) -> int:
    """The receiver's pass over ``query`` real tokens; the ``layers`` also
    see ``prefix`` keys; the head runs once, for the first reply token."""
    L = conf["num_hidden_layers"]
    causal_keys = query * (query + 1) // 2
    return (L * (2 * layer_matmul_params(conf) * query
                 + attn_flops(conf, causal_keys))
            + len(layers) * attn_flops(conf, query * prefix)
            + head_flops(conf))


def decode_row_flops(conf: dict, own: int, prefix: int,
                     layers: Sequence[int]) -> int:
    """One decode token of one live row that sees ``own`` keys of its own
    (the new token included) and, at the ``layers``, ``prefix``."""
    L = conf["num_hidden_layers"]
    return (L * (2 * layer_matmul_params(conf) + attn_flops(conf, own))
            + len(layers) * attn_flops(conf, prefix) + head_flops(conf))


def decode_attn_call(conf: dict, layer: int, keys: Sequence[int]):
    """(FLOPs, bytes) one decode-attention call requires for live rows that
    see ``keys[i]`` keys each, at any layer: read q, the valid keys and
    values, write the output, all in bfloat16."""
    Hq, Hkv, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    n = sum(keys)
    flops = 4 * Hq * D * n
    nbytes = BF16 * (2 * Hkv * D * n + 2 * Hq * D * len(keys))
    return flops, nbytes


# -- the reference -------------------------------------------------------------
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.maximum(s, 1e-30)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, policy):
    if policy == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """x: (S, H, D); pos: (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


class Geometry:
    """The sizes of a configuration file, hashable for jit."""

    def __init__(self, conf: dict):
        self.d = conf["hidden_size"]
        self.Hq = conf["num_attention_heads"]
        self.Hkv = conf["num_key_value_heads"]
        self.D = conf["head_dim"]
        self.L = conf["num_hidden_layers"]
        self.theta = float(conf["rope_theta"])
        self.eps = float(conf["rms_norm_eps"])
        self._key = (self.d, self.Hq, self.Hkv, self.D, self.L, self.theta,
                     self.eps)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Geometry) and self._key == other._key


def _layer_params(blocks, l):
    run = blocks[0]
    a, m = run["attn"], run["mlp"]
    get = lambda t: t[l].astype(F32)
    return {"g1": 1.0 + get(run["ln1"]), "g2": 1.0 + get(run["ln2"]),
            "wq": get(a["wq"]), "wk": get(a["wk"]), "wv": get(a["wv"]),
            "wo": get(a["wo"]), "wg": get(m["w_gate"]), "wu": get(m["w_up"]),
            "wd": get(m["w_down"])}


@functools.partial(jax.jit,
                   static_argnames=("g", "policy", "chunk", "mass"))
def _layer(blocks, l, x, pos, pk, pv, pvalid, *, g: Geometry, policy: str,
           chunk: int, mass: bool = False):
    """One decoder layer over rows ``x`` (S, d) at positions ``pos``, with
    an optional prefix ``pk``/``pv`` (P, Hkv, D) whose valid entries
    ``pvalid`` every row sees.  Own rows attend causally.  Returns the new
    rows, this layer's own rotated keys and values, and the prefix mass
    (mean over heads and rows) when ``mass``."""
    p = _layer_params(blocks, l)
    S, P, G = x.shape[0], pk.shape[0], g.Hq // g.Hkv
    h = rms_norm(x, p["g1"], g.eps)
    k = rope(_mm(h, p["wk"], policy).reshape(S, g.Hkv, g.D), pos, g.theta)
    v = _mm(h, p["wv"], policy).reshape(S, g.Hkv, g.D)
    K = jnp.concatenate([pk, k], 0)
    V = jnp.concatenate([pv, v], 0)

    def rows(args):
        xc, posc, idx = args
        hc = rms_norm(xc, p["g1"], g.eps)
        q = rope(_mm(hc, p["wq"], policy).reshape(chunk, g.Hq, g.D), posc,
                 g.theta).reshape(chunk, g.Hkv, G, g.D)
        s = jnp.einsum("chgd,khd->hgck", q, K, precision=HI) / math.sqrt(g.D)
        own = jnp.arange(S)[None, :] <= idx[:, None]             # (c, S)
        allow = jnp.concatenate(
            [jnp.broadcast_to(pvalid[None, :], (chunk, P)), own], 1)
        s = jnp.where(allow[None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgck,khd->chgd", pr, V, precision=HI)
        xc = xc + _mm(o.reshape(chunk, g.Hq * g.D), p["wo"], policy)
        h2 = rms_norm(xc, p["g2"], g.eps)
        f = jax.nn.silu(_mm(h2, p["wg"], policy)) * _mm(h2, p["wu"], policy)
        xc = xc + _mm(f, p["wd"], policy)
        m = jnp.sum(pr[..., :P], axis=(0, 1, 3))                 # (c,)
        return xc, m

    n = S // chunk
    xs = (x.reshape(n, chunk, -1), pos.reshape(n, chunk),
          jnp.arange(S).reshape(n, chunk))
    out, m = jax.lax.map(rows, xs)
    prefix_mass = jnp.sum(m) / (g.Hq * S) if mass else None
    return out.reshape(S, -1), k, v, prefix_mass


@functools.partial(jax.jit, static_argnames=("g", "policy"))
def _head(final_norm, lm_head, x, *, g: Geometry, policy: str):
    h = rms_norm(x, 1.0 + final_norm.astype(F32), g.eps)
    return _mm(h, lm_head.astype(F32), policy)


def _empty_prefix(g: Geometry):
    z = jnp.zeros((0, g.Hkv, g.D), F32)
    return z, z, jnp.zeros((0,), bool)


def sender_kv(params, g: Geometry, tokens: np.ndarray, upto: int,
              policy: str = "f32", chunk: int = 512):
    """Keys and values of layers ``0 .. upto - 1`` over ``tokens`` (BOS
    included) at positions ``0 .. P-1``: lists of (P, Hkv, D)."""
    P = len(tokens)
    chunk = math.gcd(P, chunk)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    pos = jnp.arange(P)
    pk, pv, pval = _empty_prefix(g)
    ks, vs = [], []
    for l in range(upto):
        x, k, v, _ = _layer(params["blocks"], l, x, pos, pk, pv, pval, g=g,
                            policy=policy, chunk=chunk)
        ks.append(k)
        vs.append(v)
    return ks, vs


def int8_wire(x):
    """Symmetric int8 with one scale per tensor, decoded back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def receiver_logits(params, g: Geometry, prefix: Dict[int, Tuple], P: int,
                    tokens: np.ndarray, rows: Sequence[int], pad_to: int,
                    policy: str = "f32", chunk: int = 128):
    """Logits at ``rows`` of the receiver's run over ``tokens`` at
    positions ``P + j``.  ``prefix`` maps each selected layer to its
    (keys, values) of P entries; they are padded to ``pad_to`` entries and
    the pad is masked.  ``tokens`` may be padded at the end: rows attend
    causally, so a pad never reaches an earlier row."""
    T = len(tokens)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    pos = P + jnp.arange(T)
    empty = _empty_prefix(g)
    valid = jnp.arange(pad_to) < P
    pad = ((0, pad_to - P), (0, 0), (0, 0))
    for l in range(g.L):
        if l in prefix:
            k, v = prefix[l]
            pk, pv, pval = jnp.pad(k, pad), jnp.pad(v, pad), valid
        else:
            pk, pv, pval = empty
        x, _, _, _ = _layer(params["blocks"], l, x, pos, pk, pv, pval, g=g,
                            policy=policy, chunk=math.gcd(T, chunk))
    logits = _head(params["final_norm"], params["lm_head"], x, g=g,
                   policy=policy)
    return logits[jnp.asarray(np.asarray(rows))]


def selection(params, g: Geometry, context: np.ndarray, query: np.ndarray,
              bos: int, ratio: float, alpha: float, sigma: float = 10.0
              ) -> Tuple[Tuple[int, ...], np.ndarray]:
    """The layers KVComm selects from one calibration sample, and the raw
    per-layer prefix masses."""
    ctx = np.concatenate([[bos], context]).astype(np.int32)
    P = len(ctx)
    ks, vs = sender_kv(params, g, ctx, g.L)
    x = params["embed"][jnp.asarray(query)].astype(F32)
    T = len(query)
    pos = P + jnp.arange(T)
    masses = []
    for l in range(g.L):
        x, _, _, m = _layer(params["blocks"], l, x, pos, ks[l], vs[l],
                            jnp.ones((P,), bool), g=g, policy="f32",
                            chunk=math.gcd(T, 128), mass=True)
        masses.append(float(m))
    raw = np.asarray(masses, np.float64)
    s = (raw - raw.min()) / max(raw.max() - raw.min(), 1e-9)
    lay = np.arange(1, g.L + 1)
    prior = np.exp(-np.square(lay - g.L / 2) / (2 * sigma ** 2))
    score = alpha * s + (1 - alpha) * prior
    m = min(g.L, max(1, math.ceil(ratio * g.L)))
    top = np.argsort(-score, kind="stable")[:m]
    return tuple(sorted(int(i) for i in top)), raw


def hop_logits(params, g: Geometry, layers: Sequence[int], bos: int,
               context: np.ndarray, query: np.ndarray, reply: np.ndarray,
               pad_to: int, policy: str = "f32", row_pad: int = 128):
    """Logits of one served request at every position that chose a reply
    token: the last query row and each reply row but the last."""
    ctx = np.concatenate([[bos], context]).astype(np.int32)
    P = len(ctx)
    ks, vs = sender_kv(params, g, ctx, max(layers) + 1, policy=policy)
    prefix = {l: (int8_wire(ks[l]), int8_wire(vs[l])) for l in layers}
    del ks, vs
    toks = np.concatenate([query, reply[:-1]]).astype(np.int32)
    T = len(toks)
    Tp = -(-T // row_pad) * row_pad
    toks = np.pad(toks, (0, Tp - T))
    rows = np.arange(len(query) - 1, T)
    return receiver_logits(params, g, prefix, P, toks, rows, pad_to,
                           policy=policy)
