"""The plain reference: a Llama-style decoder and the KVComm hop, in float32.

Written from the published descriptions, not from the program: RMSNorm,
rotary embeddings (rotate-half, base ``rope_theta``), grouped-query
attention with softmax in float32, a SwiGLU MLP, no biases, an untied
head.  The KVComm hop (arXiv:2510.03346, section 3) is:

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
