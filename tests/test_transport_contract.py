"""The Transport conformance contract.

ONE parameterized contract held over every transport — {InMemory,
Serialized, Remote/loopback} x {packed, dense} x {homogeneous,
heterogeneous assignment} x wire dtypes:

  * receiver logits: bit-exact vs ``InMemoryTransport`` (same cache
    layout) for lossless wires (model dtype / fp32), bounded relative
    delta for the lossy ones (fp16 / int8; see ``assert_lossy_close``);
  * measured bytes == the analytic ``kv_wire_bytes`` prediction (incl. the
    int8 per-layer scales);
  * ``TransferRecord`` latency stamping, the ``sync=False`` deferred-stamp
    path, ``flush_latency`` / ``poll_latency`` semantics — identical
    behavior whichever transport is underneath.

Every future transport should add itself to ``TRANSPORTS`` below and pass
unchanged."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.comm import (InMemoryTransport, RemoteTransport,
                        SerializedTransport, WirePlan)
from repro.core.types import KVCommConfig
from repro.models import transformer as tfm

# name -> (factory(packed, sync), wire itemsize or None for int8)
TRANSPORTS = {
    "mem": lambda **kw: InMemoryTransport(**kw),
    "ser_fp32": lambda **kw: SerializedTransport("float32", **kw),
    "ser_fp16": lambda **kw: SerializedTransport("float16", **kw),
    "ser_int8": lambda **kw: SerializedTransport("int8", **kw),
    "rem_fp32": lambda **kw: RemoteTransport("float32", **kw),
    "rem_fp16": lambda **kw: RemoteTransport("float16", **kw),
    "rem_int8": lambda **kw: RemoteTransport("int8", **kw),
}
# lossless = the receiver's logits must be bit-identical to InMemory
LOSSLESS = {"mem", "ser_fp32", "rem_fp32"}
ITEMSIZE = {"mem": 4, "ser_fp32": 4, "rem_fp32": 4,
            "ser_fp16": 2, "rem_fp16": 2, "ser_int8": 1, "rem_int8": 1}
PACKING = {"packed": True, "dense": False}

KVCFG = KVCommConfig(ratio=0.5, selector="prior_only")


def expected_bytes(cfg, B, Sc, M, name) -> int:
    """The analytic wire prediction per transport: KV payload at the wire
    itemsize, plus the per-layer fp32 scales an int8 wire ships."""
    n = core.kv_wire_bytes(cfg, B, Sc, M, itemsize=ITEMSIZE[name])
    if name.endswith("int8"):
        n += 2 * M * 4          # k and v scales: (M,1,1,1,1) float32 each
    return n


def assert_lossy_close(got, ref, bound: float = 0.05) -> None:
    """A lossy wire's receiver logits stay within ``bound`` of the
    reference's peak magnitude.  Greedy tokens are deliberately NOT
    compared: with random weights the reference's top two logits can sit
    1e-3 apart while int8 rounding moves logits by ~3e-2, so an argmax
    may flip on a near-tie.  The bound already rules out every flip whose
    reference margin exceeds twice the observed error."""
    rel = np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-9)
    assert rel < bound, f"lossy wire drifted {rel:.3f} rel"


@pytest.fixture(scope="module")
def homo(tiny_cfg, tiny_params):
    """Sender KV + selection + a query for the homogeneous matrix."""
    cfg = tiny_cfg
    ctx = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 4,
                             cfg.vocab_size)
    qry = jax.random.randint(jax.random.PRNGKey(2), (2, 5), 4,
                             cfg.vocab_size)
    kv, _ = core.sender_prefill(tiny_params, cfg, ctx)
    select = core.make_selection(cfg, KVCFG)
    return cfg, tiny_params, kv, select, qry


@pytest.fixture(scope="module")
def ref_logits(homo):
    """The InMemoryTransport receiver logits per cache layout — the
    reference every cell of that layout is held against.  (Packed vs dense
    agreement is ``test_packed``'s subject; different programs need not
    round identically on every CPU.)"""
    cfg, params, kv, select, qry = homo
    refs = {}
    for packing, packed in PACKING.items():
        shared = InMemoryTransport(packed=packed).send(cfg, KVCFG, kv,
                                                       select)
        out = core.receiver_prefill(params, cfg, qry, shared, max_new=0)
        refs[packing] = np.asarray(out.logits)
    return refs


class TestHomogeneousContract:
    @pytest.mark.parametrize("packing", sorted(PACKING))
    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_logits_vs_inmemory(self, homo, ref_logits, name, packing):
        cfg, params, kv, select, qry = homo
        t = TRANSPORTS[name](packed=PACKING[packing])
        shared = t.send(cfg, KVCFG, kv, select)
        assert shared.is_packed == PACKING[packing]
        out = core.receiver_prefill(params, cfg, qry, shared, max_new=0)
        got = np.asarray(out.logits)
        if name in LOSSLESS:
            np.testing.assert_array_equal(got, ref_logits[packing])
        else:
            assert_lossy_close(got, ref_logits[packing])

    @pytest.mark.parametrize("packing", sorted(PACKING))
    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_measured_bytes_match_analytics(self, homo, name, packing):
        cfg, _, kv, select, qry = homo
        t = TRANSPORTS[name](packed=PACKING[packing])
        t.send(cfg, KVCFG, kv, select)
        M = int(np.asarray(select).sum())
        B, Sc = int(kv["k"].shape[1]), int(kv["k"].shape[2])
        assert t.total_bytes == expected_bytes(cfg, B, Sc, M, name)
        assert t.last.layers == M
        assert t.last.context_len == Sc

    def test_remote_frame_overhead_is_accounted(self, homo):
        """The frame (header + CRC) is real overhead the payload count must
        NOT hide: frame_bytes strictly exceeds n_bytes, and only the remote
        transport reports it."""
        cfg, _, kv, select, _ = homo
        rem = RemoteTransport("float16")
        ser = SerializedTransport("float16")
        rem.send(cfg, KVCFG, kv, select)
        ser.send(cfg, KVCFG, kv, select)
        assert rem.last.n_bytes == ser.last.n_bytes
        assert rem.last.frame_bytes > rem.last.n_bytes
        assert ser.last.frame_bytes == 0


class TestLatencyContract:
    """Stamping semantics are part of the Transport contract — every
    implementation must behave identically."""

    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_sync_send_stamps(self, homo, name):
        cfg, _, kv, select, _ = homo
        t = TRANSPORTS[name]()
        t.send(cfg, KVCFG, kv, select)
        assert t.last.latency_s > 0.0
        assert not t._pending

    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_async_defers_then_flush_settles(self, homo, name):
        cfg, _, kv, select, _ = homo
        t = TRANSPORTS[name](sync=False)
        t.send(cfg, KVCFG, kv, select)
        assert t.last.latency_s == 0.0        # deferred, not yet measured
        assert t.flush_latency() == 1
        assert t.last.latency_s > 0.0
        assert t.flush_latency() == 0         # idempotent

    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_later_synced_send_settles_backlog(self, homo, name):
        cfg, _, kv, select, _ = homo
        t = TRANSPORTS[name]()
        t.send(cfg, KVCFG, kv, select, sync=False)
        t.send(cfg, KVCFG, kv, select, sync=True)
        assert all(r.latency_s > 0.0 for r in t.log)
        assert not t._pending

    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_poll_releases_drained(self, homo, name):
        cfg, _, kv, select, _ = homo
        t = TRANSPORTS[name](sync=False)
        shared = t.send(cfg, KVCFG, kv, select)
        jax.block_until_ready(shared)
        assert t.poll_latency() == 1
        assert not t._pending and t.last.latency_s > 0.0

    def test_remote_breakdown_sums_into_latency(self, homo):
        cfg, _, kv, select, _ = homo
        t = RemoteTransport("float16")
        t.send(cfg, KVCFG, kv, select)
        r = t.last
        assert r.serialize_s > 0 and r.deserialize_s > 0
        assert r.channel_s >= 0
        assert r.serialize_s + r.channel_s + r.deserialize_s \
            <= r.latency_s + 1e-6


# ---------------------------------------------------------------------------
# heterogeneous assignment across the same matrix
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def hetero(tok, tiny_cfg, tiny_params):
    """A 4-layer sender mapped into a 6-layer receiver."""
    r_cfg = dataclasses.replace(tiny_cfg, num_layers=6)
    r_params = tfm.init_params(r_cfg, jax.random.PRNGKey(7))
    ctx = jax.random.randint(jax.random.PRNGKey(3), (2, 7), 4,
                             tiny_cfg.vocab_size)
    qry = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 4,
                             tiny_cfg.vocab_size)
    kv, _ = core.sender_prefill(tiny_params, tiny_cfg, ctx)
    assignment = core.get_layer_map("depth_proportional").assign(
        (0, 1, 3), num_src_layers=4, num_dst_layers=6)
    return tiny_cfg, r_cfg, r_params, kv, assignment, qry


@pytest.fixture(scope="module")
def hetero_ref(hetero):
    """The mapped InMemory receiver logits per cache layout."""
    s_cfg, r_cfg, r_params, kv, assignment, qry = hetero
    refs = {}
    for packing, packed in PACKING.items():
        shared = InMemoryTransport(packed=packed).send(
            s_cfg, KVCFG, kv, None, assignment=assignment)
        out = core.receiver_prefill(r_params, r_cfg, qry, shared,
                                    max_new=0)
        refs[packing] = np.asarray(out.logits)
    return refs


class TestHeterogeneousContract:
    @pytest.mark.parametrize("packing", sorted(PACKING))
    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_mapped_logits_and_bytes(self, hetero, hetero_ref, name,
                                     packing):
        s_cfg, r_cfg, r_params, kv, assignment, qry = hetero
        t = TRANSPORTS[name](packed=PACKING[packing])
        shared = t.send(s_cfg, KVCFG, kv, None, assignment=assignment)
        # RECEIVER-keyed view whichever transport moved it
        np.testing.assert_array_equal(
            np.asarray(shared.select), np.asarray(assignment.dst_mask()))
        if PACKING[packing]:
            assert shared.layers == tuple(assignment.dst)
            assert shared.src_layers == tuple(assignment.src)
        out = core.receiver_prefill(r_params, r_cfg, qry, shared, max_new=0)
        got = np.asarray(out.logits)
        if name in LOSSLESS:
            np.testing.assert_array_equal(got, hetero_ref[packing])
        else:
            assert_lossy_close(got, hetero_ref[packing])
        # bytes track the mapped pair count P (receiver-side accounting)
        B, Sc = int(kv["k"].shape[1]), int(kv["k"].shape[2])
        assert t.total_bytes == expected_bytes(
            s_cfg, B, Sc, assignment.num_pairs, name)
        assert t.last.layers == assignment.num_pairs


# ---------------------------------------------------------------------------
# the paged column: every transport with a PageStore attached
# ---------------------------------------------------------------------------
PAGE_LEN = 3    # deliberately does NOT divide Sc=8 — the tail page pads


def expected_paged_bytes(cfg, B, Sc, M, name, pages_sent) -> int:
    n = core.kv_wire_bytes_paged(cfg, B, Sc, M, page_len=PAGE_LEN,
                                 pages_sent=pages_sent,
                                 itemsize=ITEMSIZE[name])
    if name.endswith("int8"):
        n += 2 * M * 4          # k and v scales: (M,1,1,1,1) float32 each
    return n


class TestPagedContract:
    """Attaching a ``repro.store.PageStore`` must be invisible to the
    receiver (same logits bar as the unpaged column — bit-exact on
    lossless wires) while the byte accounting switches to the paged
    analytics with full dedup on a repeat send."""

    @pytest.mark.parametrize("packing", sorted(PACKING))
    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_paged_logits_vs_unpaged(self, homo, ref_logits, name,
                                     packing):
        from repro.store import PageStore
        cfg, params, kv, select, qry = homo
        t = TRANSPORTS[name](packed=PACKING[packing],
                             store=PageStore(page_len=PAGE_LEN))
        shared = t.send(cfg, KVCFG, kv, select)
        assert shared.is_packed == PACKING[packing]
        out = core.receiver_prefill(params, cfg, qry, shared, max_new=0)
        got = np.asarray(out.logits)
        if name in LOSSLESS:
            np.testing.assert_array_equal(got, ref_logits[packing])
        else:
            # paged and unpaged int8 hand over bit-identical payloads (the
            # pages rebuild the same wire), so the unpaged bound holds
            assert_lossy_close(got, ref_logits[packing])

    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_paged_bytes_reconcile(self, homo, name):
        """Measured bytes == the paged analytics at the record's own
        pages_sent; a repeat send dedups to zero payload (int8 still ships
        its per-layer scales — they are needed to rebuild hit pages)."""
        from repro.store import PageStore
        cfg, _, kv, select, _ = homo
        t = TRANSPORTS[name](store=PageStore(page_len=PAGE_LEN))
        t.send(cfg, KVCFG, kv, select)
        M = int(np.asarray(select).sum())
        B, Sc = int(kv["k"].shape[1]), int(kv["k"].shape[2])
        pages = M * -(-Sc // PAGE_LEN)
        r = t.last
        assert (r.pages_total, r.pages_sent, r.pages_hit) == (pages, pages,
                                                              0)
        assert r.hit_rate == 0.0
        assert r.n_bytes == expected_paged_bytes(cfg, B, Sc, M, name,
                                                 pages)
        t.send(cfg, KVCFG, kv, select)
        r2 = t.last
        assert (r2.pages_total, r2.pages_sent, r2.pages_hit) == (pages, 0,
                                                                 pages)
        assert r2.hit_rate == 1.0
        assert r2.n_bytes == expected_paged_bytes(cfg, B, Sc, M, name, 0)

    @pytest.mark.parametrize("name", ["mem", "ser_fp32", "rem_fp32"])
    def test_paged_hetero_logits(self, hetero, hetero_ref, name):
        """The paged path under a LayerAssignment: receiver-keyed view,
        bit-exact on lossless wires, bytes track the mapped pair count."""
        from repro.store import PageStore
        s_cfg, r_cfg, r_params, kv, assignment, qry = hetero
        t = TRANSPORTS[name](store=PageStore(page_len=PAGE_LEN))
        shared = t.send(s_cfg, KVCFG, kv, None, assignment=assignment)
        assert shared.layers == tuple(assignment.dst)
        assert shared.src_layers == tuple(assignment.src)
        out = core.receiver_prefill(r_params, r_cfg, qry, shared,
                                    max_new=0)
        np.testing.assert_array_equal(np.asarray(out.logits),
                                      hetero_ref["packed"])
        B, Sc = int(kv["k"].shape[1]), int(kv["k"].shape[2])
        P = assignment.num_pairs
        assert t.last.layers == P
        assert t.total_bytes == expected_paged_bytes(
            s_cfg, B, Sc, P, name, P * -(-Sc // PAGE_LEN))

    @pytest.mark.parametrize("name", sorted(TRANSPORTS))
    def test_paged_latency_contract_holds(self, homo, name):
        """The deferred-stamp semantics survive the store routing."""
        from repro.store import PageStore
        cfg, _, kv, select, _ = homo
        t = TRANSPORTS[name](sync=False, store=PageStore(page_len=PAGE_LEN))
        t.send(cfg, KVCFG, kv, select)
        assert t.last.latency_s == 0.0
        assert t.flush_latency() == 1
        assert t.last.latency_s > 0.0


# ---------------------------------------------------------------------------
# the adaptive-plan column: per-layer wire precision over the same matrix
# ---------------------------------------------------------------------------
PLAN = WirePlan(("float16", "int8", "int4"))     # one slot per tier
PLAN_TRANSPORTS = {
    "ser_plan": lambda **kw: SerializedTransport(PLAN, **kw),
    "rem_plan": lambda **kw: RemoteTransport(PLAN.spec, **kw),
}


def expected_plan_bytes(cfg, B, Sc, plan) -> int:
    """Unpaged adaptive wire: per-slot analytic widths plus one fp32 scale
    per quantized slot per tensor (k and v)."""
    return core.kv_wire_bytes(cfg, B, Sc, len(plan), plan=plan) \
        + 2 * plan.n_scaled() * 4


def expected_plan_paged_bytes(cfg, B, Sc, plan, pages_sent) -> int:
    """Paged adaptive wire: the block table always carries a FULL-M fp32
    scale row per tensor (1.0 fillers at float slots) so hit pages can be
    rebuilt without re-contacting the sender."""
    return core.kv_wire_bytes_paged(cfg, B, Sc, len(plan),
                                    page_len=PAGE_LEN,
                                    pages_sent=pages_sent, plan=plan) \
        + 2 * len(plan) * 4


@pytest.fixture(scope="module")
def plan_homo(tiny_cfg, tiny_params, homo):
    """The homogeneous payload under the plan's own selection: M=3 slots
    so every precision tier is exercised, plus the InMemory reference
    logits for that selection."""
    cfg, params, kv, _, qry = homo
    select = jnp.array([True, True, True, False])
    refs = {}
    for packing, packed in PACKING.items():
        shared = InMemoryTransport(packed=packed).send(cfg, KVCFG, kv,
                                                       select)
        out = core.receiver_prefill(params, cfg, qry, shared, max_new=0)
        refs[packing] = np.asarray(out.logits)
    return cfg, params, kv, select, qry, refs


class TestPlanContract:
    @pytest.mark.parametrize("packing", sorted(PACKING))
    @pytest.mark.parametrize("name", sorted(PLAN_TRANSPORTS))
    def test_plan_logits_bounded(self, plan_homo, name, packing):
        cfg, params, kv, select, qry, ref = plan_homo
        t = PLAN_TRANSPORTS[name](packed=PACKING[packing])
        shared = t.send(cfg, KVCFG, kv, select)
        assert shared.is_packed == PACKING[packing]
        out = core.receiver_prefill(params, cfg, qry, shared, max_new=0)
        assert_lossy_close(np.asarray(out.logits), ref[packing])

    @pytest.mark.parametrize("packing", sorted(PACKING))
    @pytest.mark.parametrize("name", sorted(PLAN_TRANSPORTS))
    def test_plan_bytes_reconcile(self, plan_homo, name, packing):
        """Measured == the plan-aware ``kv_wire_bytes`` plus the quantized
        slots' scales, and the record carries the plan spec."""
        cfg, _, kv, select, _, _ = plan_homo
        t = PLAN_TRANSPORTS[name](packed=PACKING[packing])
        t.send(cfg, KVCFG, kv, select)
        B, Sc = int(kv["k"].shape[1]), int(kv["k"].shape[2])
        assert t.total_bytes == expected_plan_bytes(cfg, B, Sc, PLAN)
        # NOTE: this hand-picked one-slot-per-tier plan averages 9.3
        # bits/value; the <= uniform-int8 guarantee is a property of
        # ``WirePlan.from_scores`` defaults, asserted in test_wire_codec
        assert t.last.wire_dtype == PLAN.spec
        assert t.last.layers == len(PLAN)

    @pytest.mark.parametrize("dt", ["float32", "float16", "bfloat16",
                                    "int8", "int4"])
    def test_device_roundtrip_bit_parity_per_dtype(self, plan_homo, dt):
        """``device_wire_roundtrip`` (the async paged path's codec) is
        bit-par with the host encode->decode path for every dtype a plan
        can assign — the two implementations cannot drift silently."""
        from repro.comm.transport import (decode_wire,
                                          device_wire_roundtrip,
                                          encode_wire)
        _, _, kv, _, _, _ = plan_homo
        x = jnp.asarray(kv["k"])[:3]
        wire, _ = encode_wire(x, dt)
        host = np.asarray(decode_wire(wire, dt, x.dtype))
        dev = np.asarray(device_wire_roundtrip(x, dt, x.dtype))
        np.testing.assert_array_equal(host, dev)

    def test_device_roundtrip_bit_parity_whole_plan(self, plan_homo):
        from repro.comm.transport import (decode_wire,
                                          device_wire_roundtrip,
                                          encode_wire)
        _, _, kv, _, _, _ = plan_homo
        x = jnp.asarray(kv["k"])[:len(PLAN)]
        wire, _ = encode_wire(x, PLAN)
        host = np.asarray(decode_wire(wire, PLAN, x.dtype))
        dev = np.asarray(device_wire_roundtrip(x, PLAN, x.dtype))
        np.testing.assert_array_equal(host, dev)

    @pytest.mark.parametrize("name", sorted(PLAN_TRANSPORTS))
    def test_plan_paged_bytes_and_dedup(self, plan_homo, name):
        """The paged column under a plan: cold bytes == the plan-aware
        paged analytics + the full-M scale tables; a repeat send dedups
        every page and ships only the scales."""
        from repro.store import PageStore
        cfg, params, kv, select, qry, _ = plan_homo
        t = PLAN_TRANSPORTS[name](store=PageStore(page_len=PAGE_LEN))
        shared = t.send(cfg, KVCFG, kv, select)
        B, Sc = int(kv["k"].shape[1]), int(kv["k"].shape[2])
        pages = len(PLAN) * -(-Sc // PAGE_LEN)
        r = t.last
        assert (r.pages_total, r.pages_sent, r.pages_hit) == (pages, pages,
                                                              0)
        assert r.n_bytes == expected_plan_paged_bytes(cfg, B, Sc, PLAN,
                                                      pages)
        t.send(cfg, KVCFG, kv, select)
        r2 = t.last
        assert (r2.pages_total, r2.pages_sent, r2.pages_hit) == (pages, 0,
                                                                 pages)
        assert r2.n_bytes == expected_plan_paged_bytes(cfg, B, Sc, PLAN, 0)
        # the paged receiver view is bit-identical to the unpaged plan
        # wire (same codec, same scales — paging is pure plumbing)
        unpaged = PLAN_TRANSPORTS[name]().send(cfg, KVCFG, kv, select)
        for part in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(shared.packed_kv[part]),
                np.asarray(unpaged.packed_kv[part]))

    def test_plan_pages_never_alias_across_precision(self, plan_homo):
        """Content-addressing under mixed precision: the slot dtype joins
        the page hash, so the SAME bytes at different precisions get
        disjoint page IDs while same-dtype slots still dedup."""
        from repro.comm import WirePlan
        from repro.store.paging import split_payload
        _, _, kv, select, _, _ = plan_homo
        payload = {p: jnp.asarray(kv[p])[:3] for p in ("k", "v")}
        kw = dict(layers=(0, 1, 2), select=np.asarray(select),
                  page_len=PAGE_LEN)
        _, pages_a = split_payload(payload, wire_dtype=PLAN, **kw)
        _, pages_b = split_payload(
            payload, wire_dtype=WirePlan(("int8", "int8", "int8")), **kw)
        per_slot = -(-int(payload["k"].shape[2]) // PAGE_LEN)

        def ids(pages, slot):
            return {p.page_id
                    for p in pages[slot * per_slot:(slot + 1) * per_slot]}
        # slot 1 is int8 in BOTH plans -> identical page IDs (dedup)
        assert ids(pages_a, 1) == ids(pages_b, 1)
        # slots 0 (fp16) and 2 (int4) differ in precision -> disjoint
        assert not ids(pages_a, 0) & ids(pages_b, 0)
        assert not ids(pages_a, 2) & ids(pages_b, 2)

    @pytest.mark.parametrize("name", sorted(PLAN_TRANSPORTS))
    def test_plan_hetero_mapped(self, hetero, hetero_ref, name):
        """A length-P plan rides the heterogeneous assignment: bounded
        logits against the lossless mapped reference, bytes tracking the
        mapped pair count at per-slot widths."""
        s_cfg, r_cfg, r_params, kv, assignment, qry = hetero
        assert assignment.num_pairs == len(PLAN)
        t = PLAN_TRANSPORTS[name]()
        shared = t.send(s_cfg, KVCFG, kv, None, assignment=assignment)
        out = core.receiver_prefill(r_params, r_cfg, qry, shared, max_new=0)
        assert_lossy_close(np.asarray(out.logits), hetero_ref["packed"])
        B, Sc = int(kv["k"].shape[1]), int(kv["k"].shape[2])
        assert t.total_bytes == expected_plan_bytes(s_cfg, B, Sc, PLAN)
        assert t.last.layers == len(PLAN)
