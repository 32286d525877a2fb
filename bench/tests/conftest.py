"""The harness's tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import cells  # noqa: E402

TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  num_hidden_layers=4, vocab_size=256, bos_token_id=1,
                  pad_token_id=0, torch_dtype="float32",
                  program={"attn_impl": "chunked", "attn_block_q": 16},
                  calibration={"seed": 0, "context_tokens": 15,
                               "query_tokens": 8})
TINY_TRAFFIC = {"prefix": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                           "min": 16, "max": 48, "multiple": 16},
                "query": {"dist": "uniform", "min": 3, "max": 12},
                "max_new": {"dist": "uniform", "min": 2, "max": 6},
                "size_seed": 5}


def tiny_cell(name="reply.deepseek-llm-7b-d10", **over):
    """A cell of the given name at a CPU-sized model and traffic."""
    cell = copy.deepcopy(cells.load_cell(name))
    cell["config_file"].update(copy.deepcopy(TINY_MODEL))
    cell["traffic_file"] = copy.deepcopy(TINY_TRAFFIC)
    cell.update(capacity=3, wave=6, prefix_bucket=16, query_bucket=8,
                trace_seconds=0.1)
    cell["check"] = {"requests": 3,
                     "limits": {"max_logit_gap": 0.05,
                                "selection_mismatch": 0}}
    cell.update(over)
    return cell


@pytest.fixture
def tiny():
    return tiny_cell()
