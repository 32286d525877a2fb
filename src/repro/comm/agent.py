"""Agent: one LLM participant in a communication session.

Bundles what the old string-dispatch engine kept as loose positional state —
parameters, ``ModelConfig``, tokenizer — behind role methods.  The same
Agent type plays either side of the wire:

  sender side   : ``export_kv`` (one prefill over the context, KV + SSM
                  states out), ``message`` (NLD greedy tokens + CIPHER
                  expected embeddings), ``export_hiddens`` (AC baselines).
  receiver side : ``prefill`` / ``decode`` / ``generate`` over an optional
                  ``SharedKV`` prefix, ``calibrate`` for Eq. (1) scores.

Agents are transport-agnostic: they produce and consume ``SharedKV`` views;
``repro.comm.transport`` decides what physically crosses and counts bytes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.configs.base import ModelConfig
from repro.core.types import SharedKV
from repro.models import transformer as tfm
from repro.utils import spans


@dataclass
class Agent:
    """params + config + tokenizer, with prefill/decode/export methods."""
    name: str
    cfg: ModelConfig
    params: Any
    tok: Any

    # ---- tokenizer plumbing ----------------------------------------------
    def with_bos(self, arr: np.ndarray) -> np.ndarray:
        """Prepend BOS to every row of a (B, S) token batch."""
        b = np.full((arr.shape[0], 1), self.tok.BOS, np.int32)
        return np.concatenate([b, arr], axis=1)

    # ---- sender role ------------------------------------------------------
    def export_kv(self, context: np.ndarray, *, add_bos: bool = True
                  ) -> Tuple[Any, Any, int]:
        """One forward pass over [BOS? context]; returns (kv, states, Sc)."""
        ctx = self.with_bos(context) if add_bos else np.asarray(context)
        with spans.span(spans.SENDER_PREFILL):
            kv, states = core.sender_prefill(self.params, self.cfg,
                                             jnp.asarray(ctx))
        return kv, states, ctx.shape[1]

    def message(self, context: np.ndarray, n_tokens: int
                ) -> Tuple[np.ndarray, jnp.ndarray]:
        """Continue after [BOS context]: greedy tokens (NLD) and expected
        embeddings under the output distribution (CIPHER soft tokens)."""
        cfg, B = self.cfg, context.shape[0]
        inp = jnp.asarray(self.with_bos(context))
        cache = tfm.init_cache(cfg, B, inp.shape[1] + n_tokens)
        out = tfm.apply_model(self.params, cfg, inp, mode="cached",
                              cache=cache)
        cache = out.cache
        toks, embs = [], []
        logits = out.logits[:, -1, :]
        embed = self.params["embed"].astype(jnp.float32)
        for _ in range(n_tokens):
            nt = jnp.argmax(logits, axis=-1)[:, None]
            probs = jax.nn.softmax(logits, axis=-1)
            embs.append(probs @ embed)
            toks.append(np.asarray(nt[:, 0]))
            o = tfm.apply_model(self.params, cfg, nt, mode="cached",
                                cache=cache, logits_mode="last")
            cache, logits = o.cache, o.logits[:, -1, :]
        return np.stack(toks, 1), jnp.stack(embs, 1)

    def export_hiddens(self, context: np.ndarray) -> jnp.ndarray:
        """Last-token hidden state at every attention layer's input over
        [BOS context] — the AC baselines' wire payload. Shape (L, B, D)."""
        out = tfm.apply_model(self.params, self.cfg,
                              jnp.asarray(self.with_bos(context)),
                              mode="train", capture_hidden=True)
        return out.hiddens

    # ---- receiver role ----------------------------------------------------
    def prefill(self, tokens, shared: Optional[SharedKV] = None,
                max_new: int = 1, extra=None, prefix_lens=None):
        """Prefill over ``tokens`` with an optional sender prefix; the cache
        is sized for ``max_new`` further decode steps. ``prefix_lens``
        marks per-row real prefix lengths under a bucket-padded prefix
        (``core.pad_prefix``)."""
        return core.receiver_prefill(self.params, self.cfg,
                                     jnp.asarray(tokens), shared,
                                     max_new=max_new, extra=extra,
                                     prefix_lens=prefix_lens)

    def decode(self, token, cache, shared: Optional[SharedKV] = None):
        """One greedy decode step, eager dispatch; ``token`` is (B, 1)."""
        return core.receiver_decode(self.params, self.cfg, token, cache,
                                    shared)

    def decode_step(self, token, cache, shared: Optional[SharedKV] = None,
                    backend: str = "reference"):
        """One greedy decode step as a single jitted call with the cache
        donated — the steady-state serving path. ``backend`` picks the
        attention impl ("reference" masked-dense | "pallas" fused). Returns
        (next_token (B, 1), last_logits, new_cache); ``cache`` is consumed."""
        return core.decode_step(self.params, self.cfg, token, cache, shared,
                                backend=backend)

    def ragged_step(self, tokens, cache, shared: Optional[SharedKV],
                    prefix_lens, active, backend: str = "reference"):
        """One continuous-batching iteration over a slot-table cache: one
        donated compiled call advances every live slot by a token (rows sit
        at different generation offsets; per-row lengths mask the ragged
        tails). ``backend`` picks the attention impl ("reference"
        masked-dense | "pallas" fused two-segment kernel). Returns
        (next_tokens, logits, new cache); ``cache`` is consumed."""
        return core.ragged_decode_step(self.params, self.cfg, tokens, cache,
                                       shared, prefix_lens, active,
                                       backend=backend)

    def generate(self, tokens, shared: Optional[SharedKV] = None,
                 max_new: int = 32, extra=None):
        """Greedy generation: (tokens (B, max_new), final cache)."""
        return core.generate(self.params, self.cfg, jnp.asarray(tokens),
                             shared, max_new=max_new, extra=extra)

    def calibrate(self, query, kv, states=None) -> jnp.ndarray:
        """Eq. (1): prefill ``query`` with ALL layers shared, return the
        normalized per-layer attention-importance scores."""
        return core.calibrate(self.params, self.cfg, jnp.asarray(query),
                              kv, states)

    def self_scores(self, context: np.ndarray, query) -> jnp.ndarray:
        """Per-side Eq. (1) scores over THIS model's own layers: export the
        agent's own KV for the context and calibrate against it.  This is
        what heterogeneous pairs calibrate with — cross-model calibration
        needs matching depths, self-calibration never does; each side
        scores its own L_attn and a ``LayerMap`` aligns the two."""
        kv, states, _ = self.export_kv(context)
        return self.calibrate(query, kv, states)

    def predict_last(self, logits) -> np.ndarray:
        """argmax over the final position — the single-token answer."""
        return np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
