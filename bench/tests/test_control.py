"""The control, the reference computed with float8 products in the
program's place, is judged by ``checks.verdict`` against the cell's limits
as a run's readings are, and comes out not correct where the program's own
readings come out correct."""
import pytest

import checks
import serve
from conftest import tiny_cell


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_control_fails_the_limit(seed):
    cell = tiny_cell()
    bench = serve.Bench(cell, seed)
    bench.setup()
    # the first wave alone: how many waves 0.1 s holds varies by machine
    waves = bench.window(0.1)[:1]
    limits = cell["check"]["limits"]
    picked = checks.sample(waves, seed, len(waves[0].requests))
    r = checks.compare(bench, picked, control=True)
    correct, table = checks.verdict(r, limits)
    assert correct, table
    control_correct, control_table = checks.verdict(r["control"], limits)
    assert control_correct is False
    assert set(control_table) == set(table)
    gap = control_table["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_control_without_a_number_fails():
    limits = {"max_logit_gap": 0.15, "selection_mismatch": 0}
    assert checks.verdict({"max_logit_gap": float("inf"),
                           "selection_mismatch": 0}, limits)[0] is False
    assert checks.verdict({"max_logit_gap": float("nan"),
                           "selection_mismatch": 0}, limits)[0] is False
