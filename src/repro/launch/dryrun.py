"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost/collective analysis.

This file MUST set XLA_FLAGS before any jax import (device count locks on
first init) — hence the first two executable lines below. Do not import this
module from tests/benches; run it as a script:

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x22b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out experiments/dryrun.json
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"  # noqa: E402

import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ASSIGNED_ARCHS, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_kvcomm_prefill_fn, make_step_fn
from repro.utils.hlo import (collective_bytes, cost_analysis_dict,
                             loop_aware_collective_bytes,
                             op_census)

# combos skipped per DESIGN.md §6 (pure full-attention archs at 500k)
LONG_OK = {"rwkv6-1.6b", "zamba2-2.7b", "mixtral-8x22b", "gemma3-4b"}


def combo_skip_reason(arch: str, shape_name: str) -> str | None:
    if shape_name == "long_500k" and arch not in LONG_OK:
        return ("full-attention arch without sub-quadratic variant; "
                "skip noted in DESIGN.md §6")
    return None


def shardings_for(cfg, mesh, shape, args_spec):
    """in_shardings matching make_step_fn's argument order."""
    if shape.mode == "train":
        state_spec, batch_spec = args_spec
        pshard = shd.param_shardings(cfg, mesh, state_spec.params)
        oshard = shd.param_shardings(cfg, mesh, state_spec.opt.m)
        from repro.training.train_loop import TrainState
        from repro.training.optimizer import OptState
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        state_sh = TrainState(
            params=pshard,
            opt=OptState(step=NamedSharding(mesh, P()), m=oshard,
                         v=shd.param_shardings(cfg, mesh,
                                               state_spec.opt.v)))
        batch_sh = shd.input_shardings(cfg, mesh, shape, batch_spec)
        return (state_sh, batch_sh)
    if shape.mode == "prefill":
        params_spec, batch_spec = args_spec
        return (shd.param_shardings(cfg, mesh, params_spec),
                shd.input_shardings(cfg, mesh, shape, batch_spec))
    # decode
    params_spec, token_spec, cache_spec = args_spec
    return (shd.param_shardings(cfg, mesh, params_spec),
            shd.input_shardings(cfg, mesh, shape,
                                {"tokens": token_spec})["tokens"],
            shd.cache_shardings(cfg, mesh, shape, cache_spec))


def run_one(arch: str, shape_name: str, multi_pod: bool,
            kvcomm: bool = False, unroll: bool = False,
            moe_impl: str | None = None,
            attn_impl: str | None = None,
            microbatches: int = 1, ring_cache: bool = False) -> Dict[str, Any]:
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kvcomm": kvcomm, "unroll": unroll,
    }
    reason = combo_skip_reason(arch, shape_name)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec
    import dataclasses
    cfg = get_config(arch)
    if unroll:
        cfg = dataclasses.replace(cfg, scan_unroll=True)
    if moe_impl:
        groups = 16 if moe_impl == "dropping" else 1
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl,
                                  moe_groups=groups)
        rec["moe_impl"] = moe_impl
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
        rec["attn_impl"] = attn_impl
    if microbatches > 1:
        rec["microbatches"] = microbatches
    if ring_cache:
        cfg = dataclasses.replace(cfg, ring_cache=True)
        rec["ring_cache"] = True
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.distributed import hints
    from repro.launch.mesh import mesh_axes
    hints.set_axes(*mesh_axes(mesh))
    t0 = time.time()
    try:
        if kvcomm:
            fn, args_spec = make_kvcomm_prefill_fn(
                cfg, shape, context_len=2048)
            in_sh = (shd.param_shardings(cfg, mesh, args_spec[0]),
                     shd.input_shardings(cfg, mesh, shape, args_spec[1]),
                     shd.cache_shardings(cfg, mesh, shape, args_spec[2]),
                     shd.replicated(mesh, args_spec[3]))
        else:
            fn, args_spec = make_step_fn(cfg, shape,
                                         microbatches=microbatches)
            in_sh = shardings_for(cfg, mesh, shape, args_spec)
        donate = (0,) if shape.mode == "train" else \
                 ((2,) if shape.mode == "decode" else ())
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
            lowered = jitted.lower(*args_spec)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
        ca = cost_analysis_dict(compiled)
        rec["flops"] = float(ca.get("flops", 0.0))
        rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    rec[k] = int(v)
        txt = compiled.as_text()
        rec["collectives"] = collective_bytes(txt)
        rec["collectives_loop"] = loop_aware_collective_bytes(txt)
        rec["op_census"] = op_census(txt)
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=8)
    rec["total_s"] = round(time.time() - t0, 1)
    hints.clear()
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--kvcomm", action="store_true",
                    help="lower the KVComm receiver prefill variant")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans for exact cost_analysis "
                         "(roofline mode; slower compiles)")
    ap.add_argument("--moe-impl", default=None,
                    choices=["dense_all", "dropping"])
    ap.add_argument("--attn-impl", default=None,
                    choices=["xla", "chunked"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ring-cache", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    combos = []
    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = (list(INPUT_SHAPES) if (args.all or not args.shape)
              else [args.shape])
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                combos.append((a, s, m))

    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], r.get("kvcomm", False),
             r.get("unroll", False), r.get("moe_impl"),
             r.get("attn_impl"), "collectives_loop" in r
             or r.get("status") == "skipped")
            for r in results if r.get("status") in ("ok", "skipped")}

    for a, s, m in combos:
        key = (a, s, "2x16x16" if m else "16x16", args.kvcomm,
               args.unroll, args.moe_impl, args.attn_impl, True)
        if key in done:
            print(f"[cached] {key}")
            continue
        print(f"[dryrun] arch={a} shape={s} mesh={key[2]} "
              f"kvcomm={args.kvcomm} unroll={args.unroll}", flush=True)
        rec = run_one(a, s, m, kvcomm=args.kvcomm, unroll=args.unroll,
                      moe_impl=args.moe_impl, attn_impl=args.attn_impl,
                      microbatches=args.microbatches,
                      ring_cache=args.ring_cache)
        print(f"  -> {rec['status']} "
              f"flops={rec.get('flops', 0):.3g} "
              f"coll={rec.get('collectives', {}).get('total', 0):.3g}B "
              f"({rec.get('total_s', 0)}s)"
              + (f" ERR {rec.get('error')}" if rec["status"] == "error"
                 else ""), flush=True)
        results = [r for r in results
                   if not (r["arch"] == a and r["shape"] == s
                           and r["mesh"] == key[2]
                           and r.get("kvcomm", False) == args.kvcomm
                           and r.get("unroll", False) == args.unroll
                           and r.get("moe_impl") == args.moe_impl
                           and r.get("attn_impl") == args.attn_impl)]
        results.append(rec)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    if not args.out:
        print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
