"""Model families: the Llama family reads as the code it was moved from
did, and a family is added by files alone.

The golden values were read from the benchmark before its Llama code
moved into ``families/llama.py`` (weights, reference and counts then lived
in ``weights.py``, ``reference.py`` and ``counts.py``), on the CPU at the
tiny size of ``conftest.TINY_MODEL`` in float32; the counts at the two
configuration files as run.  Every comparison is exact.
"""
import importlib
import json
import math
import os
import sys
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

import cells
import run
import tracereduce
import weights
from conftest import tiny_cell

EXTRA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "extra_families")

WEIGHT_SUMS = {   # float64 sum of every leaf of weights.make(conf, 7)
    "['blocks'][0]['attn']['wk']": -3.2141007082464057,
    "['blocks'][0]['attn']['wo']": 13.455620111485587,
    "['blocks'][0]['attn']['wq']": 5.3202917343046465,
    "['blocks'][0]['attn']['wv']": 1.7006499758499558,
    "['blocks'][0]['ln1']": -1.1619164065632503,
    "['blocks'][0]['ln2']": -2.491849014444597,
    "['blocks'][0]['mlp']['w_down']": 3.7022817183653842,
    "['blocks'][0]['mlp']['w_gate']": 13.269655852483538,
    "['blocks'][0]['mlp']['w_up']": 28.398272708247305,
    "['embed']": 48.05032705229678,
    "['final_norm']": -0.8869122611358762,
    "['lm_head']": -13.095149897570082,
}
SELECTION = ((1, 3), [0.7840734720230103, 0.8100137114524841,
                      0.7556630373001099, 0.8017728328704834])
# (shape, sum, sum of |x|, argmax per row) of one request's hop logits
HOP = ((5, 256), 64.20391718577594, 995.514920259593, [48, 11, 106, 236, 196])
HOP_FP8 = ((5, 256), 65.4469079155242, 998.3142956498777)
# per configuration: the selected-layer count M, and each count at the
# lengths of LENGTHS
COUNTS = {
    "deepseek-llm-7b-d10": dict(
        M=5,
        sender=[2093838499840, 4230626672640, 8633052037120, 38655376752640],
        receiver=[66292285440, 135814184960, 303169536000],
        decode_row=[4928471040, 5070520320, 5651660800],
        attn_call=[(16384, 32768), (43106304, 43155456),
                   (1629290496, 1629487104)]),
    "internlm2-20b-d8": dict(
        M=4,
        sender=[3221275803648, 6494091214848, 13194340859904, 57725165764608],
        receiver=[101827215360, 207399419904, 452517691392],
        decode_row=[7428833280, 7599292416, 8296660992],
        attn_call=[(24576, 28672), (64659456, 10850304),
                   (2443935744, 407617536)]),
}
LENGTHS = dict(sender=(512, 1024, 2048, 8192),
               receiver=((16, 512), (32, 2048), (64, 8192)),
               decode_row=((1, 512), (100, 2048), (575, 8192)),
               attn_call=([1], [513, 2048, 70], [8287] * 12))


@pytest.fixture(scope="module")
def tiny_conf():
    return tiny_cell()["config_file"]


@pytest.fixture(scope="module")
def tiny_params(tiny_conf):
    return weights.make(tiny_conf, tiny_conf["weights_seed"])


def test_weights_unchanged(tiny_conf):
    leaves = jax.tree_util.tree_flatten_with_path(weights.make(tiny_conf, 7))[0]
    assert {jax.tree_util.keystr(k): float(np.asarray(v, np.float64).sum())
            for k, v in leaves} == WEIGHT_SUMS


def test_selection_unchanged(tiny_conf, tiny_params):
    fam = cells.family(tiny_conf)
    ctx, qry = cells.calibration_sample(tiny_conf)
    layers, raw = fam.selection(tiny_params, fam.Geometry(tiny_conf), ctx,
                                qry, tiny_conf["bos_token_id"], 0.5, 0.7)
    assert (layers, [float(x) for x in raw]) == SELECTION


@pytest.mark.parametrize("policy", ["f32", "fp8"])
def test_hop_logits_unchanged(tiny_conf, tiny_params, policy):
    fam = cells.family(tiny_conf)
    rng = np.random.default_rng(3)
    c, q, r = (rng.integers(0, 256, n).astype(np.int32) for n in (30, 7, 5))
    lg = np.asarray(fam.hop_logits(
        tiny_params, fam.Geometry(tiny_conf), SELECTION[0],
        tiny_conf["bos_token_id"], c, q, r, 48, policy=policy), np.float64)
    got = (lg.shape, float(lg.sum()), float(np.abs(lg).sum()))
    if policy == "f32":
        assert got + ([int(i) for i in lg.argmax(-1)],) == HOP
    else:
        assert got == HOP_FP8


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_unchanged(name):
    """The counts take layer indices: any M of them read as M did."""
    conf = cells._load("configs", name)
    fam, want = cells.family(conf), COUNTS[name]
    L, M = conf["num_hidden_layers"], want["M"]
    assert M == math.ceil(0.5 * L)
    for layers in (tuple(range(M)), tuple(range(L))[-M:], range(0, 2 * M, 2)):
        assert [fam.sender_prefill_flops(conf, n)
                for n in LENGTHS["sender"]] == want["sender"]
        assert [fam.receiver_prefill_flops(conf, q, p, layers)
                for q, p in LENGTHS["receiver"]] == want["receiver"]
        assert [fam.decode_row_flops(conf, o, p, layers)
                for o, p in LENGTHS["decode_row"]] == want["decode_row"]
    for layer in range(L):
        assert [fam.decode_attn_call(conf, layer, k)
                for k in LENGTHS["attn_call"]] == want["attn_call"]


@pytest.fixture
def recorded(monkeypatch):
    """The test family ``extra_families/recorded.py``, found by putting its
    directory on the family lookup path."""
    import families
    monkeypatch.setattr(families, "__path__", [*families.__path__, EXTRA])
    monkeypatch.delitem(sys.modules, "families.recorded", raising=False)
    mod = importlib.import_module("families.recorded")
    mod.CALLS.clear()
    yield mod
    sys.modules.pop("families.recorded", None)


def test_a_family_added_by_files_alone(recorded, capsys, monkeypatch):
    cell = tiny_cell()
    cell["config_file"]["family"] = "recorded"
    assert cells.family(cell["config_file"]) is recorded
    # the CPU runs no Pallas kernel: one stand-in op of 1 ms lets the
    # roofline reader count its calls
    monkeypatch.setattr(tracereduce.Trace, "kernel_ops",
                        lambda self, module: [NS(start=0, end=10**6)])
    for trace in (0, 1):
        argv = ["--workload", "reply.deepseek-llm-7b-d10", "--seed",
                str(2**31 + 21), "--seconds", "0.1", "--trace", str(trace)]
        assert run.main(argv, cell=cell, require_tpu=False) == 0
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert res["correct"] is True and res["failed"] == 0
        if trace:
            assert {"mfu", "ragged_decode_roofline"} <= set(res["metrics"])
    assert set(recorded.CALLS) == set(recorded.HOOKS), recorded.CALLS


def test_an_unknown_family_is_an_error():
    with pytest.raises(ValueError, match="unknown model family 'nosuch'"):
        cells.family({"family": "nosuch"})
    with pytest.raises(ValueError, match="not a module name"):
        cells.family({"family": "../llama"})
    assert cells.family({}).__name__ == "families.llama"
