"""The fused Pallas ragged-decode path: kernel-vs-oracle sweeps over the
two-segment packed layout, defined zeros for dead slots, and end-to-end
backend conformance — scheduler/serial token parity across the transport
matrix with the compile counts pinned per backend."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import (Agent, CommSession, InMemoryTransport,
                        RemoteTransport, SerializedTransport)
from repro.core.protocol import DECODE_BACKENDS, TRACE_COUNTS
from repro.core.types import KVCommConfig
from repro.data.synthetic import SyntheticTask, TaskConfig
from repro.kernels import ref
from repro.kernels.ragged_decode import ragged_decode
from repro.models import transformer as tfm
from repro.serving.scheduler import (Scheduler, SchedulerConfig,
                                     make_requests, serve_serial)

KEY = jax.random.PRNGKey(3)
KVCFG = KVCommConfig(ratio=0.5, selector="prior_only")


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape).astype(dtype)


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------
class TestRaggedDecodeKernel:
    """ragged_decode against the pure-jnp two-segment oracle."""

    @pytest.mark.parametrize("B,S,prefix_len,Hq,Hkv,D,blk_k", [
        (2, 24, 8, 4, 2, 16, 8),     # GQA, aligned blocks
        (2, 24, 8, 4, 2, 16, 7),     # odd blk_k, non-multiple
        (3, 5, 0, 2, 2, 32, 256),    # no prefix segment, S < blk_k
        (2, 40, 16, 8, 2, 64, 16),   # wide GQA, big prefix
        (1, 17, 4, 6, 3, 16, 4),     # ragged everything
    ])
    def test_matches_oracle(self, B, S, prefix_len, Hq, Hkv, D, blk_k):
        ks = jax.random.split(KEY, 5)
        q = _rand(ks[0], (B, Hq, D))
        k = _rand(ks[1], (B, S, Hkv, D))
        v = _rand(ks[2], (B, S, Hkv, D))
        kv_len = jax.random.randint(ks[3], (B,), prefix_len + 1, S + 1)
        pfx = (jax.random.randint(ks[4], (B,), 0, prefix_len + 1)
               if prefix_len else None)
        out = ragged_decode(q, k, v, kv_len, pfx, prefix_len=prefix_len,
                            blk_k=blk_k)
        rout = ref.ragged_decode_reference(q, k, v, kv_len=kv_len,
                                           prefix_lens=pfx,
                                           prefix_len=prefix_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(rout),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("seed", range(4))
    def test_segment_mask_equals_bool_predicate(self, seed):
        """The kernel's integer-select mask equals the two-segment bool
        predicate ``rk < P ? rk < pfx : rk < kv_len`` (and ``rk < Skv``)
        on every block of random rows, dead rows (kv_len = pfx = 0)
        included."""
        from repro.kernels.ragged_decode import segment_mask
        rng = np.random.default_rng(seed)
        for _ in range(16):
            prefix_len = int(rng.integers(0, 24))
            S = prefix_len + int(rng.integers(1, 40))
            blk_k = int(rng.integers(1, 48))
            B = int(rng.integers(1, 6))
            kv_len = rng.integers(prefix_len, S + 1, B)
            pfx = rng.integers(0, prefix_len + 1, B)
            dead = rng.random(B) < 0.3
            kv_len[dead], pfx[dead] = 0, 0
            nk = -(-S // blk_k)
            # (B, nk, blk_k): every block of every row, padded tail too
            rk = jnp.arange(nk * blk_k, dtype=jnp.int32).reshape(
                1, nk, blk_k)
            kl = jnp.asarray(kv_len, jnp.int32)[:, None, None]
            pf = jnp.asarray(pfx, jnp.int32)[:, None, None]
            want = jnp.where(rk < prefix_len, rk < pf, rk < kl) & (rk < S)
            got = segment_mask(rk, kl, pf, prefix_len=prefix_len, seq_kv=S)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_prefix_free_matches_flash_decode_oracle(self):
        """With prefix_len=0 the two-segment mask degenerates to the plain
        ragged mask — the kernel must agree with decode_reference."""
        ks = jax.random.split(KEY, 4)
        B, S = 3, 32
        q = _rand(ks[0], (B, 4, 16))
        k = _rand(ks[1], (B, S, 2, 16))
        v = _rand(ks[2], (B, S, 2, 16))
        kv_len = jax.random.randint(ks[3], (B,), 1, S + 1)
        out = ragged_decode(q, k, v, kv_len, blk_k=8)
        rout = ref.decode_reference(q, k, v, kv_len=kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(rout),
                                   atol=2e-5, rtol=2e-5)

    def test_zeroed_prefix_equals_unselected_layer(self):
        """pfx=0 masks the whole bucket: the row attends only to the self
        segment — exactly what unselected layers see on the dense path."""
        ks = jax.random.split(KEY, 3)
        B, P, S = 2, 8, 24
        q = _rand(ks[0], (B, 4, 16))
        k = _rand(ks[1], (B, S, 2, 16))
        v = _rand(ks[2], (B, S, 2, 16))
        kv_len = jnp.array([P + 5, P + 9], jnp.int32)
        pfx0 = jnp.zeros((B,), jnp.int32)
        out = ragged_decode(q, k, v, kv_len, pfx0, prefix_len=P, blk_k=8)
        # equivalent geometry with the bucket physically removed
        k2 = k[:, P:]
        v2 = v[:, P:]
        rout = ref.decode_reference(q, k2, v2, kv_len=kv_len - P)
        np.testing.assert_allclose(np.asarray(out), np.asarray(rout),
                                   atol=2e-5, rtol=2e-5)

    @given(st.integers(0, 3), st.integers(1, 20))
    @settings(max_examples=12, deadline=None)
    def test_dead_rows_return_zeros(self, n_dead, seed):
        """kv_len == 0 rows (retired/never-admitted slots) must return
        DEFINED zeros — not NaN, not softmax-of-nothing garbage — whatever
        the dead rows' buffers hold. Mirrors the scheduler's dead-slot
        inertness property."""
        rng = np.random.default_rng(seed)
        B, S, P = 4, 24, 8
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = _rand(ks[0], (B, 4, 16))
        k = _rand(ks[1], (B, S, 2, 16))
        v = _rand(ks[2], (B, S, 2, 16))
        kv_len = jnp.asarray(rng.integers(P + 1, S + 1, (B,)), jnp.int32)
        pfx = jnp.asarray(rng.integers(0, P + 1, (B,)), jnp.int32)
        dead = rng.choice(B, size=min(n_dead, B), replace=False)
        kv_len = kv_len.at[dead].set(0)
        pfx = pfx.at[dead].set(0)
        # poison the dead rows' caches with huge garbage
        k = k.at[dead].set(1e4 * np.sign(rng.standard_normal(
            (len(dead), S, 2, 16))).astype(np.float32))
        out = np.asarray(ragged_decode(q, k, v, kv_len, pfx, prefix_len=P,
                                       blk_k=8))
        assert np.all(np.isfinite(out))
        np.testing.assert_array_equal(out[dead], 0.0)
        # live rows unperturbed by the poisoned dead rows
        live = np.setdiff1d(np.arange(B), dead)
        if len(live):
            rout = np.asarray(ref.ragged_decode_reference(
                q, k, v, kv_len=kv_len, prefix_lens=pfx, prefix_len=P))
            np.testing.assert_allclose(out[live], rout[live],
                                       atol=2e-5, rtol=2e-5)

    def test_garbage_beyond_lengths_is_inert(self):
        """Positions past kv_len and inside the masked bucket tail never
        leak into the output."""
        ks = jax.random.split(KEY, 3)
        B, S, P = 2, 24, 8
        q = _rand(ks[0], (B, 4, 16))
        k = _rand(ks[1], (B, S, 2, 16))
        v = _rand(ks[2], (B, S, 2, 16))
        kv_len = jnp.array([P + 4, P + 7], jnp.int32)
        pfx = jnp.array([3, 6], jnp.int32)
        base = ragged_decode(q, k, v, kv_len, pfx, prefix_len=P, blk_k=8)
        idx = jnp.arange(S)
        masked = ((idx[None, :] < P) & (idx[None, :] >= pfx[:, None])) \
            | (idx[None, :] >= kv_len[:, None])
        poison = jnp.where(masked[:, :, None, None], 1e6, 0.0)
        dirty = ragged_decode(q, k + poison, v - poison, kv_len, pfx,
                              prefix_len=P, blk_k=8)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(dirty))


# ---------------------------------------------------------------------------
# backend conformance: pallas vs the serial reference, end to end
# ---------------------------------------------------------------------------
def _session(tiny_cfg, tok, transport):
    cfg = dataclasses.replace(tiny_cfg, vocab_size=tok.vocab_size)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    return CommSession(Agent("s", cfg, params, tok),
                       Agent("r", cfg, params, tok), transport)


def _stream(tok, n=6, max_new=(4, 2, 1)):
    batches = [SyntheticTask(tok, TaskConfig("retrieval", num_facts=nf,
                                             seed=11 + nf)).batch(n // 2)
               for nf in (4, 8)]
    reqs = make_requests(batches, pad=tok.PAD)[:n]
    for i, r in enumerate(reqs):
        r.max_new = max_new[i % len(max_new)]
    return reqs


class TestBackendConformance:
    """Acceptance: scheduler(decode_backend='pallas') is token-identical to
    the serial masked-dense reference across the transport/packing matrix
    and selection ratios — the kernel and the oracle disagree nowhere the
    serving loop can reach."""

    @pytest.mark.parametrize("transport", [
        lambda: InMemoryTransport(),
        lambda: InMemoryTransport(packed=False),
        lambda: SerializedTransport("float32"),
        lambda: RemoteTransport("float32"),
    ], ids=["mem_packed", "mem_dense", "ser_packed", "rem_packed"])
    def test_tokens_match_serial(self, tiny_cfg, tok, transport):
        sess = _session(tiny_cfg, tok, transport())
        reqs = _stream(tok)
        ser, _ = serve_serial(sess, reqs, KVCFG)   # reference backend
        got, _ = Scheduler(sess, KVCFG, config=SchedulerConfig(
            capacity=3, prefix_bucket=8, query_bucket=4,
            decode_backend="pallas")).run(reqs)
        assert [c.rid for c in got] == [c.rid for c in ser]
        for a, b in zip(ser, got):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    @pytest.mark.parametrize("ratio", [0.3, 0.5])
    def test_ratio_sweep(self, tiny_cfg, tok, ratio):
        kvcfg = KVCommConfig(ratio=ratio, selector="prior_only")
        sess = _session(tiny_cfg, tok, InMemoryTransport())
        reqs = _stream(tok, n=4, max_new=(3, 2))
        ser, _ = serve_serial(sess, reqs, kvcfg)
        got, _ = Scheduler(sess, kvcfg, config=SchedulerConfig(
            capacity=2, prefix_bucket=8, query_bucket=4,
            decode_backend="pallas")).run(reqs)
        for a, b in zip(ser, got):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_serial_pallas_matches_serial_reference(self, tiny_cfg, tok):
        """The serial loop's single-row decode (dense cache, no packing)
        also dispatches to the kernel."""
        sess = _session(tiny_cfg, tok, InMemoryTransport())
        reqs = _stream(tok, n=4, max_new=(4, 3))
        ser, _ = serve_serial(sess, reqs, KVCFG)
        pal, _ = serve_serial(sess, reqs, KVCFG, backend="pallas")
        for a, b in zip(ser, pal):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_unknown_backend_rejected(self, tiny_cfg, tok):
        from repro import core
        with pytest.raises(ValueError, match="backend"):
            core.decode_step(None, tiny_cfg, None, None, None,
                             backend="triton")
        assert set(DECODE_BACKENDS) == {"reference", "pallas"}

    def test_hetero_stream_parity(self, tok):
        """Depth-mismatched pair (6-layer sender -> 10-layer receiver,
        share_mapped): the packed mapped view decodes token-identically
        under both backends."""
        from repro.configs.registry import get_config

        def cfg_l(L):
            return dataclasses.replace(
                get_config("llama3.2-3b-pair"),
                num_layers=L, d_model=64, d_ff=128, num_heads=4,
                num_kv_heads=2, head_dim=16, vocab_size=tok.vocab_size,
                dtype="float32", remat=False, tie_embeddings=False)

        cs, cr = cfg_l(6), cfg_l(10)
        sess = CommSession(
            Agent("s", cs, tfm.init_params(cs, jax.random.PRNGKey(6)), tok),
            Agent("r", cr, tfm.init_params(cr, jax.random.PRNGKey(10)),
                  tok),
            InMemoryTransport())
        batch = SyntheticTask(tok, TaskConfig("retrieval", num_facts=4,
                                              seed=11)).batch(2)
        shared, _ = sess.share_mapped(batch["context"], KVCFG,
                                      policy="depth_proportional")
        qry = sess.receiver.with_bos(batch["query"])
        ref_toks = np.stack(list(sess.stream(qry, shared, max_new=6)), 1)
        pal_toks = np.stack(list(sess.stream(qry, shared, max_new=6,
                                             backend="pallas")), 1)
        np.testing.assert_array_equal(ref_toks, pal_toks)


class TestBackendTraceCounts:
    """The per-backend compile contract: switching backends costs exactly
    one ragged-step compile per (selection, table geometry) — and reruns
    over the same buckets compile nothing."""

    def test_one_pallas_compile_then_reuse(self, tiny_cfg, tok):
        sess = _session(tiny_cfg, tok, InMemoryTransport())
        cfg_s = SchedulerConfig(capacity=5, prefix_bucket=8, query_bucket=4,
                                decode_backend="pallas")
        reqs = _stream(tok, n=6, max_new=(5, 3, 1))
        base = dict(TRACE_COUNTS)
        Scheduler(sess, KVCFG, config=cfg_s).run(reqs)
        after = dict(TRACE_COUNTS)
        d_pal = after.get("ragged_decode_step[pallas]", 0) \
            - base.get("ragged_decode_step[pallas]", 0)
        assert d_pal == 1, f"expected one pallas step compile, saw {d_pal}"
        # the legacy aggregate counter tracks the same trace
        assert after.get("ragged_decode_step", 0) \
            - base.get("ragged_decode_step", 0) == 1
        # no reference-backend step traced
        assert after.get("ragged_decode_step[reference]", 0) \
            == base.get("ragged_decode_step[reference]", 0)
        # same buckets, same backend: zero further compiles
        more = _stream(tok, n=6, max_new=(4, 2, 5))
        for r in more:
            r.rid += 100
        Scheduler(sess, KVCFG, config=cfg_s).run(reqs + more)
        for key in ("ragged_decode_step", "ragged_decode_step[pallas]",
                    "receiver_prefill", "scheduler_insert"):
            assert TRACE_COUNTS.get(key, 0) == after.get(key, 0), \
                (key, dict(TRACE_COUNTS), after)

    def test_backend_switch_is_one_extra_compile(self, tiny_cfg, tok):
        """A reference-warmed scheduler switching to pallas pays exactly
        the one new step trace — admission prefill/insert executables are
        backend-independent and reused."""
        sess = _session(tiny_cfg, tok, InMemoryTransport())
        reqs = _stream(tok, n=4, max_new=(3, 2))
        kw = dict(capacity=3, prefix_bucket=8, query_bucket=4)
        Scheduler(sess, KVCFG,
                  config=SchedulerConfig(**kw)).run(reqs)       # warm ref
        base = dict(TRACE_COUNTS)
        Scheduler(sess, KVCFG, config=SchedulerConfig(
            decode_backend="pallas", **kw)).run(reqs)
        assert TRACE_COUNTS.get("ragged_decode_step[pallas]", 0) \
            - base.get("ragged_decode_step[pallas]", 0) == 1
        for key in ("receiver_prefill", "scheduler_insert"):
            assert TRACE_COUNTS.get(key, 0) == base.get(key, 0), \
                (key, dict(TRACE_COUNTS), base)
