"""A rehearsal of ``bench/run.py`` at a tiny size, kernels interpreted."""
import json

import pytest

import run


def _result(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_and_is_correct(tiny, capsys, trace):
    argv = ["--workload", "reply.deepseek-llm-7b-d10", "--seed", str(2**31 + 7),
            "--seconds", "0.1", "--trace", str(trace)]
    assert run.main(argv, cell=tiny, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is True
    assert res["attempted"] >= 6 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"max_logit_gap", "selection_mismatch"}
    names = set(res["metrics"])
    if trace:
        assert "slot_occupancy" in names
        assert res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert names == {"tokens_per_s", "ttft_p90_ms", "itl_ms", "setup_s"}


def test_shared_sessions_through_the_page_store(capsys):
    """A cell made of data alone: Zipf-shared sessions with the program's
    page store attached."""
    from conftest import TINY_TRAFFIC, tiny_cell
    traffic = dict(TINY_TRAFFIC, sharing={"contexts": 2, "zipf": 1.0})
    cell = tiny_cell(store={"page_len": 8})
    cell["traffic_file"] = traffic
    argv = ["--workload", "reply.deepseek-llm-7b-d10", "--seed", "9",
            "--seconds", "0.1", "--trace", "0"]
    assert run.main(argv, cell=cell, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_no_accelerator_means_no_result(tiny, capsys):
    argv = ["--workload", "reply.deepseek-llm-7b-d10", "--seed", "1",
            "--seconds", "0.1", "--trace", "0"]
    with pytest.raises(SystemExit) as e:
        run.main(argv, cell=tiny)
    assert e.value.code != 0
    assert capsys.readouterr().out.strip() == ""
