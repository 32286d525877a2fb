"""Ahead-of-time compiles of a cell's largest programs for a described TPU
v5e, with their memory as the compiler reckons it.  Nothing runs.

    JAX_PLATFORMS=cpu python3 bench/tools/aot.py <cell>[:<capacity>] ...

Per program: arguments, outputs, temporaries, and their sum less aliased
bytes, in GiB, for the largest shapes the cell's traffic draws: the sender
prefill at the longest prefix, the receiver prefill and the slot insert at
the largest (prefix, query) buckets, and the ragged decode step over the
full slot table.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import cells  # noqa: E402
import weights  # noqa: E402


def main(names) -> None:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro import core
    from repro.core.protocol import (_ragged_decode_step_jit,
                                     _receiver_prefill_jit,
                                     _sender_prefill_jit, extract_kv)
    from repro.core.types import KVCommConfig
    from repro.models import transformer as tfm
    from repro.serving.scheduler import _insert_jit

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    sds = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt,
                                                           sharding=one)
    jax.default_backend = lambda: "tpu"   # the kernels pick Mosaic

    for arg in names:
        name, _, cap = arg.partition(":")
        cell = cells.load_cell(name)
        if cap:
            cell.update(capacity=int(cap), wave=2 * int(cap))
        conf = cell["config_file"]
        cfg = cells.family(conf).model_config(conf)
        kvcfg = KVCommConfig(ratio=cell["ratio"], alpha=cell["alpha"],
                             selector="prior_only")
        select = core.make_selection(cfg, kvcfg)
        layers = core.selected_layer_ids(select)
        sizes = cells.wave_sizes(cell["traffic_file"], cell["wave"])
        pb, qb = cell["prefix_bucket"], cell["query_bucket"]
        P = max(s.prefix for s in sizes)
        dst = -(-P // pb) * pb
        qmax = -(-max(s.query for s in sizes) // qb) * qb
        budget = max(s.max_new for s in sizes) - 1
        cap = cell["capacity"]
        idx = jnp.asarray(layers, jnp.int32)

        def shared_of(B, S):
            # the selected layers of what the sender exports at length S
            kv = extract_kv(cfg, tfm.init_cache(cfg, B, S))
            payload = {p: kv[p][idx] for p in ("k", "v")}
            return core.build_packed(kvcfg, payload, layers, S,
                                     select=select)

        params = put(jax.eval_shape(lambda: weights.make(conf, 0)))
        progs = {}
        progs["sender_prefill"] = _sender_prefill_jit.lower(
            params, cfg, sds((1, P)), None)
        sh1 = put(jax.eval_shape(lambda: shared_of(1, dst)))
        progs["receiver_prefill"] = _receiver_prefill_jit.lower(
            params, cfg, sds((1, qmax)), sh1, budget, None,
            prefix_lens=sds((1,)))

        def table_of():
            z = shared_of(cap, dst)
            t = tfm.init_cache(cfg, cap, qmax + budget, shared=z)
            t["len"] = jnp.full((cap,), dst, jnp.int32)
            return t, z.meta()

        table, meta = jax.eval_shape(table_of)
        row = jax.eval_shape(lambda: tfm.init_cache(
            cfg, 1, qmax + budget, shared=shared_of(1, dst)))
        row["len"] = jax.ShapeDtypeStruct((), jnp.int32)
        progs["insert"] = _insert_jit.lower(
            put(table), put(row), sds(()), sds(()), src_prefix=dst,
            dst_prefix=dst, row_max_len=qmax + budget)
        progs["ragged_decode_step"] = _ragged_decode_step_jit.lower(
            params, cfg, sds((cap, 1)), put(table), put(meta),
            sds((cap,)), sds((cap,), jnp.bool_), backend="pallas")
        for pname, low in progs.items():
            ma = low.compile().memory_analysis()
            gib = lambda b: b / 2**30
            total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
            print(f"{name} {pname}: args {gib(ma.argument_size_in_bytes):.2f}"
                  f" out {gib(ma.output_size_in_bytes):.2f}"
                  f" temp {gib(ma.temp_size_in_bytes):.2f}"
                  f" alias {gib(ma.alias_size_in_bytes):.2f}"
                  f" total {gib(total):.2f} GiB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
