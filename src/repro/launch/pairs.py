"""The trained sender/receiver pair: config, tokenizer, tasks, checkpoints.

Single home for the communication pair's definition — the tiny
Llama-3.2-family stand-in trained from scratch on the synthetic task suite —
so the serving launcher, the examples, and the benchmark harness all load
the same pair without ``sys.path`` games.  Checkpoints are produced by
``examples/train_comm_pair.py`` (which imports these definitions) and land
in ``experiments/ckpt/{base,sender,receiver}.npz``; when absent,
``load_pair`` quick-trains a single model for both roles so every entry
point still runs end to end.  That 8-layer float32 stand-in is the CPU
default.

``load_pair(arch=...)`` instead serves a registry configuration at its
published widths (bfloat16, full vocabulary) with random weights made from a
seed on the device — the accelerator path, which never reads
``experiments/``.  Sender and receiver share one parameter tree: the
paper's pair #6 is two fine-tunes of one base, and two copies of a
3B-parameter model would not fit one 16 GB chip.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Any, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.configs.registry import get_config
from repro.data.synthetic import SyntheticTask, TaskConfig
from repro.data.tokenizer import SymbolTokenizer
from repro.training import checkpoint
from repro.training.optimizer import OptimizerConfig
from repro.training.train_loop import train

_REPO_ROOT = os.path.join(os.path.dirname(__file__), "..", "..", "..")
CKPT_DIR = os.path.join(_REPO_ROOT, "experiments", "ckpt")


def pair_tokenizer() -> SymbolTokenizer:
    return SymbolTokenizer(num_entities=32, num_attributes=16)


def pair_config() -> ModelConfig:
    """Tiny Llama-3.2-family stand-in: 8 layers so layer selection has room
    to matter, float32 for CPU numerics."""
    tok = pair_tokenizer()
    return dataclasses.replace(
        get_config("llama3.2-3b-pair"),
        num_layers=8, d_model=192, d_ff=512, num_heads=6, num_kv_heads=6,
        head_dim=32, vocab_size=tok.vocab_size, dtype="float32",
        remat=False, tie_embeddings=False)


def deep_receiver_config() -> ModelConfig:
    """The heterogeneous counterpart: a DEEPER receiver (12 layers vs the
    pair's 8) with identical per-layer KV geometry (Hkv, Dh) and the same
    tokenizer — the real depth-mismatched pair the LayerMap policies are
    exercised on (8-layer sender -> 12-layer receiver)."""
    return dataclasses.replace(pair_config(), num_layers=12)


def task_suite(tok: SymbolTokenizer, seed: int = 0):
    """The training mixture: the Countries / HotpotQA / Tipsheets analogues."""
    return [
        SyntheticTask(tok, TaskConfig("retrieval", num_facts=4, seed=seed)),
        SyntheticTask(tok, TaskConfig("retrieval", num_facts=6,
                                      seed=seed + 1)),
        SyntheticTask(tok, TaskConfig("retrieval", num_facts=8,
                                      seed=seed + 2)),
        SyntheticTask(tok, TaskConfig("multihop", num_facts=6, hops=2,
                                      seed=seed + 3)),
        SyntheticTask(tok, TaskConfig("decision", num_options=3,
                                      seed=seed + 4)),
    ]


def _quick_train(cfg, tok, steps: int = 1200, ckpt_name: str = "base"):
    from repro.data.pipeline import mixed_lm_iter
    print(f"[pairs] no checkpoint found -> quick-training {steps} steps "
          f"({ckpt_name}; run examples/train_comm_pair.py for the full "
          "pair)", file=sys.stderr)
    it = mixed_lm_iter(task_suite(tok, seed=0), 64, seed=0)
    opt = OptimizerConfig(lr=2e-3, total_steps=steps,
                          warmup_steps=steps // 20)
    state = train(cfg, opt, it, steps=steps, log_every=0)
    # cache as a shared checkpoint so the next entry point skips the
    # quick-train (load_pair prefers sender/receiver fine-tunes)
    try:
        os.makedirs(CKPT_DIR, exist_ok=True)
        checkpoint.save(os.path.join(CKPT_DIR, ckpt_name), state.params,
                        {"role": ckpt_name, "quick_train_steps": steps})
    except OSError as e:
        print(f"[pairs] could not cache quick-train checkpoint: {e}",
              file=sys.stderr)
    return state.params


_CACHE: dict = {}


def load_pair(arch=None, seed: int = 0
              ) -> Tuple[ModelConfig, SymbolTokenizer, Any, Any]:
    """(cfg, tok, sender_params, receiver_params). Uses the trained
    checkpoints when available, else quick-trains a single model for both
    roles (the protocol is still exercised end to end).  With ``arch`` (a
    registry name) the pair is that config at full width with weights drawn
    from ``seed``; see ``seeded_pair``."""
    if arch is not None:
        return seeded_pair(arch, seed)
    if "pair" in _CACHE:
        return _CACHE["pair"]
    cfg, tok = pair_config(), pair_tokenizer()
    template = _param_template(cfg)
    s_path = os.path.join(CKPT_DIR, "sender.npz")
    r_path = os.path.join(CKPT_DIR, "receiver.npz")
    b_path = os.path.join(CKPT_DIR, "base.npz")
    if os.path.exists(s_path) and os.path.exists(r_path):
        sender = checkpoint.restore(s_path, template)
        receiver = checkpoint.restore(r_path, template)
    elif os.path.exists(b_path):
        sender = receiver = checkpoint.restore(b_path, template)
    else:
        sender = receiver = _quick_train(cfg, tok)
    _CACHE["pair"] = (cfg, tok, sender, receiver)
    return _CACHE["pair"]


def seeded_pair(arch, seed: int = 0
                ) -> Tuple[ModelConfig, SymbolTokenizer, Any, Any]:
    """The registry config ``arch`` (a name, or a ``ModelConfig`` such as a
    ``reduced()`` cut for CPU rehearsals) unchanged — published widths, its
    own dtype and vocabulary — with one parameter tree initialized by a
    jitted ``init_params`` on the default device, used for both roles.  The
    ``SymbolTokenizer`` ids are a subset of the config's vocabulary."""
    cfg = get_config(arch) if isinstance(arch, str) else arch
    key = ("seeded", cfg, seed)
    if key not in _CACHE:
        from repro.models import transformer as tfm
        tok = pair_tokenizer()
        if tok.vocab_size > cfg.vocab_size:
            raise ValueError(f"{cfg.name}: vocabulary {cfg.vocab_size} "
                             f"cannot hold the task tokenizer's "
                             f"{tok.vocab_size}")
        init = jax.jit(tfm.init_params, static_argnums=0)
        params = init(cfg, jax.random.PRNGKey(seed))
        _CACHE[key] = (cfg, tok, params, params)
    return _CACHE[key]


def _param_template(cfg: ModelConfig):
    from repro.models import transformer as tfm
    template = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(
        lambda s: jax.numpy.zeros(s.shape, s.dtype), template)


def load_hetero_pair() -> Tuple[ModelConfig, ModelConfig, SymbolTokenizer,
                                Any, Any]:
    """(sender_cfg, receiver_cfg, tok, sender_params, receiver_params): the
    trained 8-layer sender paired with a DEEPER, separately trained
    12-layer receiver (``deep_receiver_config``) — a real heterogeneous
    pair sharing tokenizer and KV geometry but not depth.  The deep
    receiver's checkpoint is cached at ``receiver_deep.npz``; when absent
    it is quick-trained once, like the base pair."""
    if "hetero" in _CACHE:
        return _CACHE["hetero"]
    s_cfg, tok, sender, _ = load_pair()
    r_cfg = deep_receiver_config()
    d_path = os.path.join(CKPT_DIR, "receiver_deep.npz")
    if os.path.exists(d_path):
        receiver = checkpoint.restore(d_path, _param_template(r_cfg))
    else:
        receiver = _quick_train(r_cfg, tok, ckpt_name="receiver_deep")
    _CACHE["hetero"] = (s_cfg, r_cfg, tok, sender, receiver)
    return _CACHE["hetero"]
