"""Ahead-of-time compiles for a described TPU v5e, at real widths.

Interpret mode accepts kernels that Mosaic refuses (block shapes off the
(8, 128) tiling, selects between bool vectors), so the main-path kernels and
the jitted ragged decode step of ``llama3.2-3b-pair`` are compiled here for
the chip itself.  Nothing runs: only shapes are given, and the compiler says
whether the chip would accept the program and how much memory it needs.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and a test worker that loads it keeps it.
"""
import pytest

import jax
import jax.numpy as jnp

from repro import core
from repro.configs.registry import get_config
from repro.core.protocol import _ragged_decode_step_jit
from repro.core.types import KVCommConfig
from repro.kernels import ops
from repro.kernels.ragged_decode import ragged_decode
from repro.models import transformer as tfm

ARCH = "llama3.2-3b-pair"
HBM_BYTES = 16 * 2**30          # one v5e chip
# the serving geometry of the one-chip smoke: 8 slots, 1,024-token contexts
# (+BOS) in a 1,040 bucket, 8-token queries and 7 decode steps
B, PREFIX, SELF = 8, 1040, 8 + 7
S = PREFIX + 1072               # a longer cache than the slot table's


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def put(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kv_shapes(cfg, seq):
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return (_sds((B, H, D)), _sds((B, seq, Hkv, D)), _sds((B, seq, Hkv, D)))


@pytest.mark.parametrize("prefix_len", [0, PREFIX])
def test_ragged_decode_compiles(put, prefix_len):
    cfg = get_config(ARCH)
    fn = jax.jit(lambda q, k, v, n, p: ragged_decode(
        q, k, v, n, p, prefix_len=prefix_len, interpret=False))
    lens = _sds((B,), jnp.int32)
    hlo = fn.lower(*put((*_kv_shapes(cfg, S), lens, lens))).compile() \
        .as_text()
    assert "tpu_custom_call" in hlo


def test_flash_decode_compiles(put):
    cfg = get_config(ARCH)
    fn = jax.jit(lambda q, k, v, n: ops.decode_attention(
        q, k, v, n, interpret=False))
    hlo = fn.lower(*put((*_kv_shapes(cfg, S), _sds((B,), jnp.int32)))) \
        .compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(put):
    cfg = get_config(ARCH)
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    fn = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, interpret=False)[0])
    hlo = fn.lower(*put((_sds((1, 1024, H, D)), _sds((1, 1024, Hkv, D)),
                         _sds((1, 1024, Hkv, D))))).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("backend", ["pallas", "reference"])
def test_ragged_decode_step_compiles(put, monkeypatch, backend):
    """The scheduler's donated ragged step over the packed slot table at
    ratio 0.5: it fits one chip, and the pallas backend really carries the
    Mosaic kernel (the step picks interpret mode from the backend, which in
    this process is the CPU — so tell it the chip is there)."""
    cfg = get_config(ARCH)
    kvcfg = KVCommConfig(ratio=0.5, selector="prior_only")
    select = core.make_selection(cfg, kvcfg)
    layers = core.selected_layer_ids(select)

    def build():
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        payload = {p: jnp.zeros((len(layers), B, PREFIX, cfg.num_kv_heads,
                                 cfg.resolved_head_dim), jnp.bfloat16)
                   for p in ("k", "v")}
        zero = core.build_packed(kvcfg, payload, layers, PREFIX,
                                 select=select)
        table = tfm.init_cache(cfg, B, SELF, shared=zero)
        table["len"] = jnp.full((B,), PREFIX, jnp.int32)
        return params, table, zero.meta()

    params, table, meta = jax.eval_shape(build)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _ragged_decode_step_jit.lower(
        put(params), cfg, put(_sds((B, 1), jnp.int32)), put(table),
        put(meta), put(_sds((B,), jnp.int32)), put(_sds((B,), jnp.bool_)),
        backend=backend).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need < HBM_BYTES, f"{need / 2**30:.2f} GiB"
