"""The unified model: embeds tokens (plus stub modality frontends), executes
the config's layer plan as a sequence of scannable runs, and projects logits.

A "run" is a maximal group of same-kind layers (``ModelConfig.layer_plan``);
parameters inside a run are stacked on a leading layer axis and executed under
``lax.scan`` — the MaxText-style trick that keeps HLO size (and compile time)
independent of depth, which matters for the 80-layer dry-runs.

One function, four modes:
  * train   : logits over the whole sequence, no cache.
  * cached  : prefill/decode with a cache (see ``init_cache``); S==1 decodes.
Supported extras: ``frames`` (whisper stub audio embeddings, (B,Senc,D)),
``patches`` (pixtral stub patch embeddings substituted into the first
``num_patches`` sequence slots).

KVComm enters through ``shared``: per-attention-layer sender KV written into
the cache prefix by ``init_cache`` plus a per-layer selection mask; see
``repro.core``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed import hints
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_moe, dense_init, embed_init,
                                 init_mlp, init_moe, rms_norm,
                                 sinusoid_positions)


class ModelOut(NamedTuple):
    logits: jnp.ndarray
    cache: Optional[Any]
    masses: Optional[jnp.ndarray]   # (n_attn_layers, B) Eq.(1) raw mass
    aux_loss: jnp.ndarray           # MoE load-balance loss (0.0 if dense)
    hiddens: Optional[jnp.ndarray] = None  # (L_attn, B, D) last-token states


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def mlp_type(cfg) -> str:
    return "gelu" if cfg.arch_type == "audio" or cfg.name.startswith(
        "starcoder") else "swiglu"


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_attn_layer(cfg, spec: LayerSpec, key):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "ln1": jnp.zeros((d,), _dt(cfg)),
        "attn": attn_mod.init_attn(ks[0], cfg),
        "ln2": jnp.zeros((d,), _dt(cfg)),
    }
    if spec.moe:
        p["moe"] = init_moe(ks[1], d, cfg.d_ff, cfg.num_experts, _dt(cfg))
    else:
        p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, _dt(cfg), mlp_type(cfg))
    if spec.cross_attn:
        p["ln_x"] = jnp.zeros((d,), _dt(cfg))
        p["xattn"] = attn_mod.init_cross_attn(ks[2], cfg)
    return p


def _init_run(cfg, spec: LayerSpec, key):
    if spec.kind == "shared_attn":
        return None  # params live once at top level
    keys = jax.random.split(key, spec.count)
    if spec.kind == "attn":
        return jax.vmap(lambda k: _init_attn_layer(cfg, spec, k))(keys)
    if spec.kind == "mamba":
        def one(k):
            return {"ln": jnp.zeros((cfg.d_model,), _dt(cfg)),
                    "mamba": ssm_mod.init_mamba(k, cfg)}
        return jax.vmap(one)(keys)
    if spec.kind == "rwkv":
        def one(k):
            return {"ln1": jnp.zeros((cfg.d_model,), _dt(cfg)),
                    "ln2": jnp.zeros((cfg.d_model,), _dt(cfg)),
                    "rwkv": ssm_mod.init_rwkv(k, cfg)}
        return jax.vmap(one)(keys)
    raise ValueError(spec.kind)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model), _dt(cfg)),
        "final_norm": jnp.zeros((cfg.d_model,), _dt(cfg)),
    }
    plan = cfg.layer_plan()
    rkeys = jax.random.split(keys[1], len(plan))
    params["blocks"] = [
        _init_run(cfg, spec, rkeys[i]) for i, spec in enumerate(plan)]
    if any(s.kind == "shared_attn" for s in plan):
        params["shared_attn"] = _init_attn_layer(
            cfg, LayerSpec(kind="attn", count=1), keys[2])
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            keys[3], (cfg.d_model, cfg.vocab_size), _dt(cfg))
    if cfg.encoder_layers:
        eplan = cfg.encoder_plan()
        ekeys = jax.random.split(keys[4], len(eplan))
        params["encoder"] = {
            "blocks": [_init_run(cfg, dataclasses.replace(s), ekeys[i])
                       for i, s in enumerate(eplan)],
            "final_norm": jnp.zeros((cfg.d_model,), _dt(cfg)),
        }
    return params


# ---------------------------------------------------------------------------
# selection partitioning (the packed fast path)
# ---------------------------------------------------------------------------
def _run_partition(attn_i: int, n: int, layers: Tuple[int, ...]):
    """Partition one attention run's n layers on the static selection.

    ``layers`` is the global selected-layer index map (``SharedKV.layers``).
    Returns (sel, unsel, segments):
      sel / unsel : local layer indices (within the run) of each stack;
      segments    : maximal contiguous blocks of same selection status, in
                    layer order, as (is_sel, start_in_stack, length) — each
                    segment is a contiguous slice of its stack because both
                    stacks preserve layer order.
    """
    sel_set = {i - attn_i for i in layers if attn_i <= i < attn_i + n}
    sel = tuple(sorted(sel_set))
    unsel = tuple(i for i in range(n) if i not in sel_set)
    segments = []
    taken = {True: 0, False: 0}
    j = 0
    while j < n:
        is_sel = j in sel_set
        j0 = j
        while j < n and (j in sel_set) == is_sel:
            j += 1
        segments.append((is_sel, taken[is_sel], j - j0))
        taken[is_sel] += j - j0
    return sel, unsel, tuple(segments)


def _indexed_layer_body(body, stack):
    """Wrap a layer body so it reads its parameters out of the run's FULL
    stacked tree by layer index (``per["layer"]``).  The partitioned scans
    of the packed fast path then never materialize a gathered copy of a
    parameter stack: a 3B-parameter model's selected + unselected stacks
    would otherwise cost a second copy of every layer weight per call."""
    def indexed(x, per):
        per = dict(per)
        i = per.pop("layer")
        per["params"] = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            stack)
        return body(x, per)
    return indexed


def _is_packed_entry(run_cache) -> bool:
    return isinstance(run_cache, dict) and "sel" in run_cache


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------
def _init_ssm_run(cfg, spec, batch, shared, ssm_i):
    init_fn = (ssm_mod.init_mamba_state if spec.kind == "mamba"
               else ssm_mod.init_rwkv_state)
    st = jax.vmap(lambda _: init_fn(cfg, batch))(jnp.arange(spec.count))
    if shared is not None and shared.states is not None:
        st = _seed_states(st, shared, ssm_i, spec.count)
    return st


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               *, shared=None, dtype=None) -> Dict[str, Any]:
    """Build the serving cache. ``shared`` is a ``repro.core.SharedKV``;
    its per-layer sender KV is written into cache positions [0, prefix_len)
    of attention runs and its states seed SSM runs (state-sharing protocol).

    A *packed* ``shared`` (static ``layers`` map) builds the
    selection-specialized cache instead: each attention run is split into a
    "sel" stack whose buffers carry the prefix and an "unsel" stack whose
    buffers are prefix-free — prefix HBM scales with M selected layers, not
    all L.
    """
    if shared is not None and shared.is_packed:
        return _init_cache_packed(cfg, batch, max_len, shared, dtype)
    dtype = dtype or _dt(cfg)
    prefix_len = 0 if shared is None else shared.prefix_len
    Smax = max_len + prefix_len
    Hkv, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    runs: List[Any] = []
    attn_i = 0   # global attention-layer index (paper's layer index l)
    ssm_i = 0
    for spec in cfg.layer_plan():
        n = spec.count
        if spec.kind in ("attn", "shared_attn"):
            S_buf = Smax
            if cfg.ring_cache and spec.window and prefix_len == 0:
                # ring buffer: a windowed layer never attends beyond the
                # last `window` positions
                S_buf = min(Smax, spec.window)
            k = jnp.zeros((n, batch, S_buf, Hkv, Dh), dtype)
            v = jnp.zeros((n, batch, S_buf, Hkv, Dh), dtype)
            ctx_valid = jnp.zeros((n,), bool)
            if shared is not None and shared.kv is not None:
                sk = shared.kv["k"][attn_i:attn_i + n].astype(dtype)
                sv = shared.kv["v"][attn_i:attn_i + n].astype(dtype)
                k = k.at[:, :, :prefix_len].set(sk)
                v = v.at[:, :, :prefix_len].set(sv)
                ctx_valid = shared.select[attn_i:attn_i + n]
            entry = {"k": k, "v": v, "ctx_valid": ctx_valid}
            if spec.cross_attn:
                Senc = cfg.encoder_seq
                entry["xk"] = jnp.zeros((n, batch, Senc, Hkv, Dh), dtype)
                entry["xv"] = jnp.zeros((n, batch, Senc, Hkv, Dh), dtype)
            runs.append(entry)
            attn_i += n
        elif spec.kind in ("mamba", "rwkv"):
            runs.append(_init_ssm_run(cfg, spec, batch, shared, ssm_i))
            ssm_i += n
    return {"len": jnp.asarray(prefix_len, jnp.int32), "runs": runs}


def _init_cache_packed(cfg: ModelConfig, batch: int, max_len: int,
                       shared, dtype=None) -> Dict[str, Any]:
    """Selection-specialized cache: per attention run, a prefix-carrying
    "sel" stack (seeded straight from the packed wire payload — no dense
    zero-padded scatter) and a prefix-free "unsel" stack."""
    dtype = dtype or _dt(cfg)
    prefix_len = shared.prefix_len
    Hkv, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    runs: List[Any] = []
    attn_i = 0
    ssm_i = 0
    packed_i = 0   # cursor into the packed (M, ...) payload, layer-ordered
    for spec in cfg.layer_plan():
        n = spec.count
        if spec.kind in ("attn", "shared_attn"):
            sel, unsel, _ = _run_partition(attn_i, n, shared.layers)
            entry = {}
            for name, idx, has_prefix in (("sel", sel, True),
                                          ("unsel", unsel, False)):
                m = len(idx)
                S_buf = max_len + (prefix_len if has_prefix else 0)
                k = jnp.zeros((m, batch, S_buf, Hkv, Dh), dtype)
                v = jnp.zeros((m, batch, S_buf, Hkv, Dh), dtype)
                if has_prefix and m and shared.packed_kv is not None:
                    sk = shared.packed_kv["k"][packed_i:packed_i + m]
                    sv = shared.packed_kv["v"][packed_i:packed_i + m]
                    k = k.at[:, :, :prefix_len].set(sk.astype(dtype))
                    v = v.at[:, :, :prefix_len].set(sv.astype(dtype))
                sub = {"k": k, "v": v,
                       "ctx_valid": jnp.full((m,), has_prefix, bool)}
                if spec.cross_attn:
                    Senc = cfg.encoder_seq
                    sub["xk"] = jnp.zeros((m, batch, Senc, Hkv, Dh), dtype)
                    sub["xv"] = jnp.zeros((m, batch, Senc, Hkv, Dh), dtype)
                entry[name] = sub
            packed_i += len(sel)
            runs.append(entry)
            attn_i += n
        elif spec.kind in ("mamba", "rwkv"):
            runs.append(_init_ssm_run(cfg, spec, batch, shared, ssm_i))
            ssm_i += n
    return {"len": jnp.asarray(prefix_len, jnp.int32), "runs": runs}


def cache_insert_row(table: Dict[str, Any], row: Dict[str, Any], slot,
                     *, src_prefix: int, dst_prefix: int,
                     row_max_len: int) -> Dict[str, Any]:
    """Copy the single row of a B==1 serving cache into row ``slot`` of a
    B==capacity slot-table cache (continuous batching admission).

    Buffers are matched leaf-by-leaf: same sequence capacity copies the row
    straight across; a smaller prefix-free buffer (its capacity equals the
    request's ``row_max_len`` = query bucket + decode budget) lands at
    offset 0; a prefix-carrying buffer whose capacity differs (the request
    was prefilled at a smaller prefix bucket ``src_prefix`` than the
    table's ``dst_prefix``) is copied as two segments — prefix
    ``[0, src_prefix)`` stays put, the self region moves from ``src_prefix``
    to ``dst_prefix``. Sound because KV entries are position-rotated by
    ABSOLUTE position, never by buffer offset. ``ctx_valid`` (per-layer
    selection flags, identical across rows of one frozen selection) and
    ``len`` (scheduler-owned, per-row) are left untouched. Jit-friendly;
    ``slot`` may be traced."""
    def put(path, t, r):
        name = getattr(path[-1], "key", None)
        if name in ("ctx_valid", "len"):
            return t
        if t.ndim < 3 or t.shape[2] == r.shape[2]:
            return t.at[:, slot].set(r[:, 0])
        if r.shape[2] == row_max_len:        # prefix-free, smaller bucket
            return t.at[:, slot, :r.shape[2]].set(r[:, 0])
        self_len = r.shape[2] - src_prefix
        t = t.at[:, slot, :src_prefix].set(r[:, 0, :src_prefix])
        return t.at[:, slot, dst_prefix:dst_prefix + self_len].set(
            r[:, 0, src_prefix:])
    new_runs = jax.tree_util.tree_map_with_path(put, table["runs"],
                                                row["runs"])
    return {"len": table["len"], "runs": new_runs}


def cache_insert_row_paged(cfg: ModelConfig, table: Dict[str, Any],
                           row: Dict[str, Any], slot, prefix, *,
                           layers: Tuple[int, ...], src_prefix: int,
                           dst_prefix: int,
                           row_max_len: int) -> Dict[str, Any]:
    """``cache_insert_row`` that consumes a page-table gather: the prefix
    region of each selected layer's slot row is written from ``prefix``
    (the ``PageStore.gather_prefix`` result — a packed
    ``{"k","v"}: (M, B, src_prefix, Hkv, Dh)`` stack rebuilt from
    content-addressed pages) instead of from the request row's own
    buffers.  The self region still comes from ``row`` exactly as in
    ``cache_insert_row``; ``ctx_valid`` and ``len`` stay untouched.

    Requires the packed (sel/unsel) attention-only cache — ``layers`` is
    the frozen selection map that partitions each run.  Bit-parity with
    ``cache_insert_row`` holds because ``gather_prefix`` at the prefix
    bucket equals the padded prefix the row was prefilled with.
    Jit-friendly; ``slot`` and ``prefix`` may be traced."""
    new_runs: List[Any] = []
    attn_i = 0
    packed_i = 0   # cursor into the packed (M, ...) prefix, layer-ordered
    for spec, t_run, r_run in zip(cfg.layer_plan(), table["runs"],
                                  row["runs"]):
        n = spec.count
        if spec.kind not in ("attn", "shared_attn") \
                or not _is_packed_entry(t_run):
            raise ValueError("cache_insert_row_paged requires the packed "
                             "(sel/unsel) attention-only cache")
        sel, _, _ = _run_partition(attn_i, n, layers)
        m = len(sel)
        entry = {}
        for name in ("sel", "unsel"):
            t_sub, r_sub = dict(t_run[name]), r_run[name]
            for part in ("k", "v"):
                t, r = t_sub[part], r_sub[part]
                if name == "sel" and m:
                    pg = prefix[part][packed_i:packed_i + m]
                    self_len = r.shape[2] - src_prefix
                    t = t.at[:, slot, :src_prefix].set(
                        pg[:, 0].astype(t.dtype))
                    t = t.at[:, slot,
                             dst_prefix:dst_prefix + self_len].set(
                        r[:, 0, src_prefix:])
                elif t.shape[2] == r.shape[2]:
                    t = t.at[:, slot].set(r[:, 0])
                else:
                    t = t.at[:, slot, :r.shape[2]].set(r[:, 0])
                t_sub[part] = t
            for part in ("xk", "xv"):
                if part in t_sub:
                    t_sub[part] = t_sub[part].at[:, slot].set(
                        r_sub[part][:, 0])
            entry[name] = t_sub
        packed_i += m
        new_runs.append(entry)
        attn_i += n
    return {"len": table["len"], "runs": new_runs}


def _seed_states(st, shared, ssm_i, n):
    sel = shared.state_select[ssm_i:ssm_i + n]
    def blend(z, s):
        if s is None:
            return z
        s = s[ssm_i:ssm_i + n].astype(z.dtype)
        w = sel.reshape((n,) + (1,) * (z.ndim - 1)).astype(z.dtype)
        return z * (1 - w) + s * w
    return jax.tree.map(blend, st, shared.states)


# ---------------------------------------------------------------------------
# run bodies
# ---------------------------------------------------------------------------
def _attn_layer_body(cfg, spec, mode, prefix_len, collect_mass, enc_out,
                     capture_hidden=False, inject_mode=None,
                     backend="reference"):
    """Returns f(x, per_layer) -> (x, ys) executing ONE attention layer."""
    mt = mlp_type(cfg)
    use_rope = cfg.arch_type != "audio"

    def body(x, per):
        p = per["params"]
        cache = per.get("cache")
        layer = (cache or {}).get("layer")   # set where stacks are carried
        cap = x[:, -1, :] if capture_hidden else None
        if inject_mode is not None:
            # AC baseline (Ramesh & Li 2025): merge the sender's last-token
            # hidden state into the receiver's at this layer's input.
            vec = per["inject_vec"].astype(x.dtype)
            last = x[:, -1, :]
            comb = {"replace": vec, "sum": last + vec,
                    "mean": 0.5 * (last + vec)}[inject_mode]
            new_last = jnp.where(per["inject_flag"], comb, last)
            x = x.at[:, -1, :].set(new_last)
        out, kv, mass = attn_mod.self_attention(
            p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
            mode=mode, causal=spec.causal, use_rope=use_rope,
            window=spec.window,
            pos_shift=per["pos_shift"],
            prefix_len=prefix_len,
            ctx_valid=(cache or {}).get("ctx_valid"),
            cache_k=(cache or {}).get("k"),
            cache_v=(cache or {}).get("v"),
            cache_layer=layer,
            cache_len=per.get("cache_len"),
            prefix_lens=per.get("prefix_lens"),
            collect_mass=collect_mass,
            backend=backend,
        )
        x = x + out
        ys = {}
        if mode == "cached":
            ys["k"], ys["v"] = kv
            ys["ctx_valid"] = cache["ctx_valid"]
        if spec.cross_attn:
            h = rms_norm(x, p["ln_x"], cfg.norm_eps)
            if mode == "cached":
                if enc_out is not None:   # prefill: build cross KV
                    xk, xv = attn_mod.cross_kv(p["xattn"], cfg, enc_out)
                    ys["xk"] = _put_layer(cache["xk"], xk, layer)
                    ys["xv"] = _put_layer(cache["xv"], xv, layer)
                else:                     # decode: reuse cached cross KV
                    ys["xk"], ys["xv"] = cache["xk"], cache["xv"]
                    xk = attn_mod.layer_of(cache["xk"], layer)
                    xv = attn_mod.layer_of(cache["xv"], layer)
            else:
                xk, xv = attn_mod.cross_kv(p["xattn"], cfg, enc_out)
            x = x + attn_mod.cross_attention(p["xattn"], cfg, h, xk, xv)
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if spec.moe:
            ffn, aux = apply_moe(p["moe"], h, cfg)
        else:
            with jax.named_scope("mlp"):
                ffn = apply_mlp(p["mlp"], h, mt)
            aux = jnp.zeros((), jnp.float32)
        x = x + ffn
        ys["aux"] = aux
        if collect_mass:
            ys["mass"] = (mass if mass is not None
                          else jnp.zeros((x.shape[0],), jnp.float32))
        if capture_hidden:
            ys["h_last"] = cap
        return x, ys

    return body


def _put_layer(cache, x, layer):
    """``x`` as one layer's new cache: the layer of a stack, written in
    place, or ``x`` itself where there is no stack (``layer is None``)."""
    if layer is None:
        return x
    return jax.lax.dynamic_update_index_in_dim(cache, x.astype(cache.dtype),
                                               layer, 0)


def _ssm_layer_body(cfg, spec, mode):
    if spec.kind == "mamba":
        def body(x, per):
            p, st = per["params"], per["cache"]
            out, new_st = ssm_mod.apply_mamba(
                p["mamba"], cfg, rms_norm(x, p["ln"], cfg.norm_eps), st,
                mode=mode)
            return x + out, new_st
        return body

    def body(x, per):  # rwkv
        p, st = per["params"], per["cache"]
        r = p["rwkv"]
        tm_out, new_wkv, new_tmx = ssm_mod.rwkv_time_mix(
            r, cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
            {"tm_x": st["tm_x"], "wkv": st["wkv"]})
        x = x + tm_out
        cm_out, new_cmx = ssm_mod.rwkv_channel_mix(
            r, cfg, rms_norm(x, p["ln2"], cfg.norm_eps),
            {"cm_x": st["cm_x"]})
        x = x + cm_out
        return x, {"wkv": new_wkv, "tm_x": new_tmx, "cm_x": new_cmx}
    return body


def _carried_cache_body(body):
    """Wrap a layer body so the run's cache stacks ride in the scan carry.

    The layer finds the whole stacks and its own index (``per["slot"]``)
    in its cache, writes its new rows into them in place and hands them on
    to the next layer: no layer's cache is sliced out of a stack through
    the scan's inputs or stacked anew through its outputs.  ``ctx_valid``
    is read, never written."""
    def step(carry, per):
        x, stacks = carry
        per = dict(per)
        i = per.pop("slot")
        per["cache"] = dict(stacks, layer=i,
                            ctx_valid=stacks["ctx_valid"][i])
        x, ys = body(x, per)
        ys.pop("ctx_valid")
        new = {kk: stacks[kk] if kk == "ctx_valid" else ys.pop(kk)
               for kk in stacks}
        return (hints.shard_activations(x), new), ys
    return step


def _apply_packed_attn_run(run_p, cfg, spec, x, run_cache, *, shared,
                           attn_i, cache_len, prefix_len, collect_mass,
                           capture_hidden, enc_out, prefix_lens=None,
                           backend="reference"):
    """Execute one attention run under the selection-specialized fast path.

    The run's cache is partitioned (static, per selection bitmask) into a
    selected stack that carries the sender prefix and an unselected stack
    that is prefix-free; layer order is preserved by scanning maximal
    contiguous same-status segments in sequence, each reading its layers'
    parameters from the unpartitioned stack by index. Prefix attention
    FLOPs therefore scale with the number of selected layers, not the run
    length, and the unselected buffers never hold (or mask) prefix entries.
    Each segment carries its whole stack and addresses its layers as
    ``s0 + i`` in it, so the step writes only the new rows and the donated
    cache is updated where it lives.
    """
    sel, unsel, segments = _run_partition(attn_i, spec.count, shared.layers)
    stacks = {name: dict(run_cache[name]) for name in ("sel", "unsel")}
    masses, hiddens = [], []
    aux = jnp.zeros((), jnp.float32)
    zero_unsel = shared.pos_mode == "zero_unselected"
    j0 = 0   # the segment's first layer within the run
    for is_sel, s0, ln in segments:
        name = "sel" if is_sel else "unsel"
        pfx = prefix_len if is_sel else 0
        clen = cache_len if is_sel else cache_len - prefix_len
        if prefix_lens is not None:
            # ragged rows: the positional shift is each row's REAL prefix
            # length (the bucket pad must not displace self positions)
            rows = (jnp.zeros_like(prefix_lens)
                    if (zero_unsel and not is_sel) else prefix_lens)
            shift_arr = jnp.broadcast_to(rows[None],
                                         (ln,) + prefix_lens.shape)
        else:
            shift = 0 if (zero_unsel and not is_sel) else prefix_len
            shift_arr = jnp.full((ln,), shift, jnp.int32)
        per = {"layer": jnp.arange(j0, j0 + ln),
               "slot": jnp.arange(s0, s0 + ln),
               "pos_shift": shift_arr,
               "cache_len": jnp.broadcast_to(clen,
                                             (ln,) + jnp.shape(clen))}
        if prefix_lens is not None and is_sel:
            per["prefix_lens"] = jnp.broadcast_to(
                prefix_lens[None], (ln,) + prefix_lens.shape)
        body = _carried_cache_body(_indexed_layer_body(
            _attn_layer_body(cfg, spec, "cached", pfx, collect_mass,
                             enc_out, capture_hidden=capture_hidden,
                             backend=backend), run_p))
        (x, stacks[name]), ys = jax.lax.scan(
            body, (x, stacks[name]), per,
            unroll=True if cfg.scan_unroll else 1)
        j0 += ln
        aux = aux + jnp.sum(ys["aux"])
        if collect_mass:
            masses.append(ys["mass"])
        if capture_hidden:
            hiddens.append(ys["h_last"])
    return x, stacks, aux, masses, hiddens


def _run_scan(body, x, per_layer, *, remat: bool, unroll: bool = False):
    if remat:
        body = jax.checkpoint(body)
    def scan_body(carry, xs):
        y, ys = body(carry, xs)
        # pin the carried residual's sharding (no-op unless a launcher
        # installed mesh hints) — keeps remat-saved per-layer residuals
        # batch/sequence-sharded instead of replicated
        return hints.shard_activations(y), ys
    return jax.lax.scan(scan_body, x, per_layer, unroll=True if unroll
                        else 1)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _embed(params, cfg, tokens, *, extra, pos_shift):
    x = params["embed"][tokens]
    if cfg.num_patches and extra and "patches" in extra:
        P = extra["patches"].shape[1]
        x = jnp.concatenate(
            [extra["patches"].astype(x.dtype), x[:, P:, :]], axis=1)
    if extra and "soft_embeds" in extra:
        # CIPHER-style soft tokens: substitute expected embeddings
        se = extra["soft_embeds"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice_in_dim(
            x, se, extra.get("soft_start", 0), axis=1)
    if cfg.arch_type == "audio":  # whisper decoder: additive sinusoid
        S = tokens.shape[1]
        pos = pos_shift + jnp.arange(S)
        x = x + sinusoid_positions(pos, cfg.d_model)[None].astype(x.dtype)
    return x


def _encoder_forward(params, cfg, frames):
    enc = params["encoder"]
    x = frames.astype(_dt(cfg))
    Senc = x.shape[1]
    x = x + sinusoid_positions(jnp.arange(Senc), cfg.d_model)[None].astype(
        x.dtype)
    for spec, run_p in zip(cfg.encoder_plan(), enc["blocks"]):
        body = _attn_layer_body(cfg, spec, "train", 0, False, None)
        per = {"params": run_p,
               "pos_shift": jnp.zeros((spec.count,), jnp.int32)}
        x, _ = _run_scan(body, x, per, remat=cfg.remat,
                         unroll=cfg.scan_unroll)
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def apply_model(
    params, cfg: ModelConfig, tokens, *,
    mode: str = "train",                 # "train" | "cached"
    cache=None,
    shared=None,                         # repro.core.SharedKV (for pos mode)
    extra: Optional[Dict[str, jnp.ndarray]] = None,
    collect_mass: bool = False,
    logits_mode: str = "all",            # "all" | "last"
    capture_hidden: bool = False,        # AC baseline: export last-token
                                         # hidden at every attn layer input
    inject: Optional[Dict[str, Any]] = None,
    # inject = {"vec": (L_attn,B,D), "mask": (L_attn,), "mode": str}
    prefix_lens: Optional[jnp.ndarray] = None,
    # (B,) real per-row prefix lengths when the shared prefix is bucket-
    # padded (ragged continuous batching); None = every row fills the bucket
    decode_backend: str = "reference",
    # decode-step (S==1) attention impl: "reference" masked-dense or
    # "pallas" fused ragged kernel; prefill/train ignore it
) -> ModelOut:
    B, S = tokens.shape
    if shared is not None and shared.is_packed and mode != "cached":
        # the packed fast path is cache-resident by construction; anything
        # else (e.g. AC-baseline train-mode calls) takes the dense view
        shared = shared.to_dense(cfg.attn_layer_count)
    prefix_len = 0 if shared is None else shared.prefix_len
    pos_mode = "shift" if shared is None else shared.pos_mode
    if prefix_len == 0 or mode != "cached":
        prefix_lens = None
    cache_is_ragged = cache is not None and jnp.ndim(cache["len"]) > 0
    if prefix_lens is not None or cache_is_ragged:
        # ragged rows carry per-row positions; the audio stack's additive
        # sinusoid embed path is scalar-shift only
        assert cfg.arch_type != "audio", \
            "ragged (continuous-batching) rows need a rope arch"

    enc_out = None
    if cfg.encoder_layers and extra and "frames" in extra:
        enc_out = _encoder_forward(params, cfg, extra["frames"])

    cache_len = cache["len"] if cache is not None else jnp.zeros((), jnp.int32)
    base_shift = jnp.asarray(prefix_len, jnp.int32)
    x = _embed(params, cfg, tokens, extra=extra,
               pos_shift=(cache_len - prefix_len) + base_shift
               if mode == "cached" else jnp.zeros((), jnp.int32))

    plan = cfg.layer_plan()
    new_runs: List[Any] = []
    masses: List[jnp.ndarray] = []
    hiddens: List[jnp.ndarray] = []
    aux_total = jnp.zeros((), jnp.float32)
    attn_i = 0

    for ri, spec in enumerate(plan):
        run_p = params["blocks"][ri]
        run_cache = cache["runs"][ri] if cache is not None else None
        n = spec.count
        if spec.kind in ("attn", "shared_attn"):
            if spec.kind == "shared_attn":
                run_p = jax.tree.map(lambda a: a[None],
                                     params["shared_attn"])
            if _is_packed_entry(run_cache):
                assert shared is not None and shared.is_packed, \
                    "packed cache needs its packed SharedKV (or its .meta())"
                assert inject is None, \
                    "AC injection runs on the dense path"
                eo = enc_out if (spec.cross_attn and not S == 1) else None
                x, entry, aux, m_list, h_list = _apply_packed_attn_run(
                    run_p, cfg, spec, x, run_cache, shared=shared,
                    attn_i=attn_i, cache_len=cache_len,
                    prefix_len=prefix_len, collect_mass=collect_mass,
                    capture_hidden=capture_hidden, enc_out=eo,
                    prefix_lens=prefix_lens, backend=decode_backend)
                aux_total = aux_total + aux
                masses.extend(m_list)
                hiddens.extend(h_list)
                new_runs.append(entry)
                attn_i += n
                continue
            # per-layer positional shift (paper default: == prefix_len
            # everywhere; KVComm-S: 0 at non-selected layers); per-row
            # real lengths replace the bucket size on ragged rows
            if prefix_len and pos_mode == "zero_unselected":
                sel = jax.lax.dynamic_slice_in_dim(
                    shared.select, attn_i, n, 0)
                if prefix_lens is not None:
                    shift = jnp.where(sel[:, None], prefix_lens[None],
                                      0).astype(jnp.int32)
                else:
                    shift = jnp.where(sel, prefix_len, 0).astype(jnp.int32)
            elif prefix_lens is not None:
                shift = jnp.broadcast_to(
                    prefix_lens[None], (n,) + prefix_lens.shape
                ).astype(jnp.int32)
            else:
                shift = jnp.full((n,), prefix_len, jnp.int32)
            per = {"params": run_p, "pos_shift": shift}
            if mode == "cached":
                per["cache"] = run_cache
                per["cache_len"] = jnp.broadcast_to(
                    cache_len, (n,) + jnp.shape(cache_len))
                if prefix_lens is not None:
                    per["prefix_lens"] = jnp.broadcast_to(
                        prefix_lens[None], (n,) + prefix_lens.shape)
            if inject is not None:
                per["inject_vec"] = jax.lax.dynamic_slice_in_dim(
                    inject["vec"], attn_i, n, 0)
                per["inject_flag"] = jax.lax.dynamic_slice_in_dim(
                    inject["mask"], attn_i, n, 0)
            eo = enc_out if (spec.cross_attn and not
                             (mode == "cached" and S == 1)) else None
            body = _attn_layer_body(
                cfg, spec, mode, prefix_len, collect_mass, eo,
                capture_hidden=capture_hidden,
                inject_mode=inject["mode"] if inject is not None else None,
                backend=decode_backend)
            remat = cfg.remat and mode == "train"
            x, ys = _run_scan(body, x, per, remat=remat,
                              unroll=cfg.scan_unroll)
            aux_total = aux_total + jnp.sum(ys["aux"])
            if collect_mass:
                masses.append(ys["mass"])
            if capture_hidden:
                hiddens.append(ys["h_last"])
            if mode == "cached":
                keys = ["k", "v", "ctx_valid"]
                if spec.cross_attn:
                    keys += ["xk", "xv"]
                new_runs.append({kk: ys[kk] for kk in keys})
            attn_i += n
        else:
            if run_cache is None:
                init_fn = (ssm_mod.init_mamba_state if spec.kind == "mamba"
                           else ssm_mod.init_rwkv_state)
                run_cache = jax.vmap(lambda _: init_fn(cfg, B))(jnp.arange(n))
            per = {"params": run_p, "cache": run_cache}
            body = _ssm_layer_body(cfg, spec, mode)
            remat = cfg.remat and mode == "train"
            x, new_st = _run_scan(body, x, per, remat=remat,
                                  unroll=cfg.scan_unroll)
            new_runs.append(new_st)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logits_mode == "last":
        x = x[:, -1:, :]
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    logits = hints.shard_logits(logits.astype(jnp.float32))

    new_cache = None
    if mode == "cached":
        new_cache = {"len": cache_len + S, "runs": new_runs}
    mass_out = jnp.concatenate(masses, axis=0) if masses else None
    hid_out = jnp.concatenate(hiddens, axis=0) if hiddens else None
    return ModelOut(logits=logits, cache=new_cache, masses=mass_out,
                    aux_loss=aux_total, hiddens=hid_out)
