"""A run with the timed path broken underneath reads ``correct: false``.

The harness's look for a chip is skipped; everything else of a run is
driven at a tiny size.  Each fault is one that a served cell can have: a
decode token altered where the step produces it, the first token altered
where admission produces it, and the shared prefix lost on the wire.
"""
import json

import jax.numpy as jnp
import pytest

import run


def _alter_decode(monkeypatch):
    from repro.comm.agent import Agent
    step = Agent.ragged_step

    def broken(self, *a, **k):
        ntok, logits, cache = step(self, *a, **k)
        return (ntok + 1) % self.cfg.vocab_size, logits, cache
    monkeypatch.setattr(Agent, "ragged_step", broken)


def _alter_first(monkeypatch):
    from repro.comm.agent import Agent
    prefill = Agent.prefill

    def broken(self, *a, **k):
        out = prefill(self, *a, **k)
        return out._replace(logits=-out.logits)
    monkeypatch.setattr(Agent, "prefill", broken)


def _lose_prefix(monkeypatch):
    from repro.comm import transport
    roundtrip = transport.roundtrip_kv

    def broken(payload, wire_dtype, dtype):
        out, n = roundtrip(payload, wire_dtype, dtype)
        return {p: jnp.zeros_like(v) for p, v in out.items()}, n
    monkeypatch.setattr(transport, "roundtrip_kv", broken)


@pytest.mark.parametrize("fault", [_alter_decode, _alter_first,
                                   _lose_prefix],
                         ids=["decode_token", "first_token", "prefix_lost"])
def test_broken_path_is_not_correct(tiny, capsys, monkeypatch, fault):
    fault(monkeypatch)
    argv = ["--workload", "reply.deepseek-llm-7b-d10", "--seed", "3",
            "--seconds", "0.1", "--trace", "0"]
    assert run.main(argv, cell=tiny, require_tpu=False) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
