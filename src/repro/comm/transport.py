"""Transports: how KV moves from sender to receiver, with exact byte
accounting.

A ``Transport`` owns the wire.  ``send`` takes the sender's full per-layer KV
stack plus the selection mask and returns the *receiver-side* ``SharedKV``
view, appending a ``TransferRecord`` to its log.  Byte counting lives here —
NOT in ``repro.core.protocol`` — because the transport runs on the host where
the selected-layer count is static (``int(jnp.sum(select))`` inside a traced
function would force a trace break).  ``send`` also stamps the record's
``latency_s`` (device-synced wall clock around the transfer) — the async
scheduler's prerequisite.

Both transports hand over the *packed* receiver view by default
(``packed=True``): the (M, B, Sc, Hkv, Dh) selected-layer payload plus its
static layer-index map, which the receiver consumes directly via the
selection-specialized cache (`repro.models.transformer._init_cache_packed`)
— no dense zero-padded scatter on either side. ``packed=False`` restores
the legacy dense (L, ...) view for the uniform-scan path.

Three implementations:

  InMemoryTransport   — hand-over of device buffers (the two agents
                        co-located in one process); packed mode gathers the
                        selected layers, dense mode is zero-copy.  Bytes are
                        the analytic payload size of the selected layers.
  SerializedTransport — actually materializes the wire payload: gathers the
                        selected layers (``gather_selected``), casts to the
                        configured wire dtype (fp16 / bf16 / int8 with
                        per-layer symmetric scales), measures ``nbytes`` from
                        the buffers themselves.  Measured bytes agree with
                        ``repro.core.channel.kv_wire_bytes`` analytics by
                        construction (asserted in tests).
  RemoteTransport     — ``repro.comm.remote``: frames the same wire payload
                        (the codec below is shared — ``encode_wire`` /
                        ``decode_wire``) and ships it through a byte channel
                        (loopback / TCP socket / shared-filesystem staging)
                        across process boundaries.

Both subsume the legacy ``repro.core.Channel`` (kept as a deprecated alias
surface for old callers); records are the same ``TransferRecord`` type so
logs interoperate.

Heterogeneous pairs: ``send(..., assignment=LayerAssignment)`` routes
through ``_send_mapped`` — the wire carries exactly the assignment's P
sender layers (a mapping policy may have dropped some of the sender's M
selected layers; only receiver-consumable KV crosses) and the record's
``layers``/bytes track P, i.e. M_receiver-side accounting.
"""
from __future__ import annotations

import abc
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.channel import TransferRecord
from repro.core.layermap import LayerAssignment
from repro.core.protocol import (build_mapped, build_packed, build_shared,
                                 gather_mapped, gather_selected, pack_mapped,
                                 pack_shared, scatter_mapped,
                                 selected_layer_ids)
from repro.core.types import KVCommConfig, SharedKV
from repro.utils import spans

_WIRE_DTYPES = {
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "int8": jnp.int8,
}

# int4 has no jnp dtype — it travels nibble-packed in uint8 (two values per
# byte along the trailing head-dim axis) with a per-layer fp32 scale
_WIRE_BITS = {"float32": 32, "bfloat16": 16, "float16": 16, "int8": 8,
              "int4": 4}
# wires whose payload carries a per-layer fp32 scale array
_SCALED_WIRES = ("int8", "int4")
# finest → coarsest; a plan ships side-band state leaves at its finest tier
_TIER_ORDER = ("float32", "bfloat16", "float16", "int8", "int4")
_PLAN_PREFIX = "plan:"


@dataclass(frozen=True)
class WirePlan:
    """A per-layer wire precision plan: ``dtypes[m]`` is the wire dtype of
    the m-th *selected* (packed-order) layer slot.  Anywhere a uniform
    ``wire_dtype`` string travels (frame headers, ``TransferRecord``,
    ``BlockTable``) a plan travels as its canonical spec string
    ``"plan:float16,int8,int4"`` — JSON-safe and order-preserving."""

    dtypes: tuple

    def __post_init__(self):
        object.__setattr__(self, "dtypes", tuple(self.dtypes))
        for d in self.dtypes:
            if d not in _WIRE_BITS:
                raise ValueError(f"unknown wire dtype {d!r} in plan; "
                                 f"expected one of {sorted(_WIRE_BITS)}")

    def __len__(self) -> int:
        return len(self.dtypes)

    @property
    def spec(self) -> str:
        return _PLAN_PREFIX + ",".join(self.dtypes)

    @classmethod
    def parse(cls, spec: str) -> "WirePlan":
        if not spec.startswith(_PLAN_PREFIX):
            raise ValueError(f"not a wire-plan spec: {spec!r}")
        body = spec[len(_PLAN_PREFIX):]
        return cls(tuple(d for d in body.split(",") if d))

    @classmethod
    def from_scores(cls, scores, select=None, *, top_frac: float = 0.25,
                    low_frac: float = 0.5, top_dtype: str = "float16",
                    mid_dtype: str = "int8",
                    low_dtype: str = "int4") -> "WirePlan":
        """Allocate precision by calibration score: the top ``top_frac`` of
        selected slots ship at ``top_dtype``, the bottom ``low_frac`` at
        ``low_dtype``, the middle at ``mid_dtype``.  ``scores`` is the
        per-layer importance over the sender's full depth (Eq. 1 combined
        scores); ``select`` the frozen boolean selection mask (``None`` =
        every layer is a slot).  With the default 16/8/4-bit tiers the low
        count is floored at twice the top count, so the plan's payload
        never exceeds a uniform int8 wire at ANY slot count (rounding the
        fractions independently can otherwise overshoot, e.g. n=6), and it
        ships fewer scale side-bands."""
        scores = np.asarray(scores, dtype=np.float64).reshape(-1)
        if select is not None:
            slots = np.nonzero(np.asarray(select).reshape(-1))[0]
            scores = scores[slots]
        n = int(scores.shape[0])
        if n == 0:
            return cls(())
        order = np.argsort(-scores, kind="stable")
        n_top = int(round(top_frac * n))
        # every 16-bit top slot must be paid for by two 4-bit low slots
        # (16 + 2*4 = 3*8) or the int8 byte bound breaks
        n_low = min(max(int(round(low_frac * n)), 2 * n_top), n - n_top)
        dtypes = [mid_dtype] * n
        for i in order[:n_top]:
            dtypes[int(i)] = top_dtype
        if n_low:
            for i in order[n - n_low:]:
                dtypes[int(i)] = low_dtype
        return cls(tuple(dtypes))

    def groups(self):
        """Slots grouped by dtype, in order of first occurrence — the
        deterministic array layout of a plan-encoded wire tuple."""
        out: Dict[str, List[int]] = {}
        for m, d in enumerate(self.dtypes):
            out.setdefault(d, []).append(m)
        return list(out.items())

    @property
    def state_dtype(self) -> str:
        """Wire dtype for side-band state leaves: the finest tier present
        in the plan (states are tiny next to KV — never down-bit them
        below the best KV tier)."""
        if not self.dtypes:
            return "float16"
        return min(set(self.dtypes), key=_TIER_ORDER.index)

    def n_scaled(self) -> int:
        """How many slots carry a per-layer scale (int8/int4)."""
        return sum(1 for d in self.dtypes if d in _SCALED_WIRES)

    def payload_bits(self) -> int:
        """Sum of per-value bit widths across slots (scales excluded)."""
        return sum(_WIRE_BITS[d] for d in self.dtypes)


def resolve_wire_dtype(wire_dtype):
    """Normalize/validate a wire dtype argument: a plain name passes
    through, a ``"plan:..."`` spec parses to a ``WirePlan``, a ``WirePlan``
    validates as-is.  Raises ``ValueError`` on anything else."""
    if isinstance(wire_dtype, WirePlan):
        return wire_dtype
    if isinstance(wire_dtype, str):
        if wire_dtype.startswith(_PLAN_PREFIX):
            return WirePlan.parse(wire_dtype)
        if wire_dtype in _WIRE_BITS:
            return wire_dtype
    raise ValueError(f"unsupported wire_dtype: {wire_dtype!r}; expected "
                     f"one of {sorted(_WIRE_BITS)} or a 'plan:...' spec")


def wire_spec(wire_dtype) -> str:
    """The JSON-safe string form of a wire dtype or plan."""
    wd = resolve_wire_dtype(wire_dtype)
    return wd.spec if isinstance(wd, WirePlan) else wd


def as_wire_plan(wire_dtype):
    """The ``WirePlan`` behind a wire dtype argument, or ``None`` for a
    uniform dtype."""
    wd = resolve_wire_dtype(wire_dtype)
    return wd if isinstance(wd, WirePlan) else None


def wire_has_scales(wire_dtype) -> bool:
    """Whether this wire ships per-layer fp32 scale side-bands."""
    wd = resolve_wire_dtype(wire_dtype)
    if isinstance(wd, WirePlan):
        return len(wd) > 0
    return wd in _SCALED_WIRES


def state_wire_dtype(wire_dtype) -> str:
    """The uniform dtype state leaves travel at for this wire."""
    wd = resolve_wire_dtype(wire_dtype)
    return wd.state_dtype if isinstance(wd, WirePlan) else wd


def wire_array_count(wire_dtype) -> int:
    """How many arrays ``encode_wire`` emits for one stacked payload part
    at this wire dtype — the framing layer's expected arity."""
    wd = resolve_wire_dtype(wire_dtype)
    if isinstance(wd, WirePlan):
        if not len(wd):
            return 1    # empty-selection sentinel: one empty array
        return sum(2 if d in _SCALED_WIRES else 1 for d, _ in wd.groups())
    return 2 if wd in _SCALED_WIRES else 1


def _pack_int4(q: np.ndarray) -> np.ndarray:
    """Nibble-pack an int8 array of values in [-8, 7] pairwise along the
    LAST axis → uint8 of half the trailing extent.  The sequence axis is
    untouched, so page slicing and streaming chunk slicing work on packed
    wires unchanged."""
    if q.shape[-1] % 2:
        raise ValueError("int4 wire requires an even trailing (head_dim) "
                         f"axis; got shape {q.shape}")
    lo = (q[..., 0::2] & 0x0F).astype(np.uint8)
    hi = (q[..., 1::2] & 0x0F).astype(np.uint8)
    return (lo | (hi << 4)).astype(np.uint8)


def _unpack_int4(p) -> jnp.ndarray:
    """Inverse of ``_pack_int4`` (jnp — runs on device in decode)."""
    p = jnp.asarray(p).astype(jnp.uint8)
    lo = (p & 0x0F).astype(jnp.int8)
    hi = ((p >> 4) & 0x0F).astype(jnp.int8)

    def sx(v):  # sign-extend 4 bits
        return jnp.where(v > 7, v - 16, v)

    pairs = jnp.stack([sx(lo), sx(hi)], axis=-1)
    return pairs.reshape(p.shape[:-1] + (p.shape[-1] * 2,))


def _int4_scale(x: jnp.ndarray) -> jnp.ndarray:
    absmax = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)),
                     keepdims=True)
    return jnp.maximum(absmax, 1e-8) / 7.0


# ---------------------------------------------------------------------------
# the wire codec — module-level so every transport that materializes a
# payload (SerializedTransport in-process, RemoteTransport cross-process)
# shares ONE cast/quantize implementation and their byte accounting can
# never diverge
# ---------------------------------------------------------------------------
def encode_wire(x: jnp.ndarray, wire_dtype):
    """Cast one stacked array (leading layer axis) to its wire form.
    Returns ``((arrays...), n_bytes)`` — one array for float wires, a
    (quantized, per-layer fp32 scales) pair for int8 (symmetric per-layer
    quantization) and int4 (nibble-packed trailing axis); the scales are
    part of the payload and counted.  A ``WirePlan`` (or ``"plan:..."``
    spec) encodes each dtype group with this same uniform codec and
    concatenates the group tuples in ``plan.groups()`` order."""
    wire_dtype = resolve_wire_dtype(wire_dtype)
    if isinstance(wire_dtype, WirePlan):
        return _encode_wire_plan(x, wire_dtype)
    if wire_dtype == "int8":
        # symmetric per-layer scales (leading axis), shipped alongside
        # the payload; works for KV stacks and SSM state leaves alike
        absmax = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)),
                         keepdims=True)
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        q = np.asarray(jnp.clip(jnp.round(x / scale), -127, 127)
                       .astype(jnp.int8))
        s = np.asarray(scale, dtype=np.float32)
        return (q, s), q.nbytes + s.nbytes
    if wire_dtype == "int4":
        scale = _int4_scale(jnp.asarray(x))
        q = np.asarray(jnp.clip(jnp.round(x / scale), -7, 7)
                       .astype(jnp.int8))
        packed = _pack_int4(q)
        s = np.asarray(scale, dtype=np.float32)
        return (packed, s), packed.nbytes + s.nbytes
    wire = np.asarray(x.astype(_WIRE_DTYPES[wire_dtype]))
    return (wire,), wire.nbytes


def _encode_wire_plan(x, plan: WirePlan):
    x = jnp.asarray(x)
    if x.shape[0] != len(plan):
        raise ValueError(f"wire plan covers {len(plan)} slots but payload "
                         f"has {x.shape[0]} layers")
    if not len(plan):
        # empty selection: a single zero-element fp16 array keeps the
        # frame layout shape-preserving while counting zero bytes
        empty = np.zeros(x.shape, np.float16)
        return (empty,), 0
    arrays, n = [], 0
    for dt, slots in plan.groups():
        wire, nb = encode_wire(x[np.asarray(slots)], dt)
        arrays.extend(wire)
        n += nb
    return tuple(arrays), n


def decode_wire(wire, wire_dtype, dtype) -> jnp.ndarray:
    """Inverse of ``encode_wire``: reconstruct the compute-dtype array from
    the wire arrays (dequantizing through fp32 for int8/int4)."""
    wire_dtype = resolve_wire_dtype(wire_dtype)
    if isinstance(wire_dtype, WirePlan):
        return _decode_wire_plan(wire, wire_dtype, dtype)
    if wire_dtype == "int8":
        q, s = wire
        return (jnp.asarray(q).astype(jnp.float32) * jnp.asarray(s)) \
            .astype(dtype)
    if wire_dtype == "int4":
        p, s = wire
        q = _unpack_int4(p)
        return (q.astype(jnp.float32) * jnp.asarray(s)).astype(dtype)
    return jnp.asarray(wire[0]).astype(dtype)


def np_encode_wire(x: np.ndarray, wire_dtype):
    """Host-side ``encode_wire`` for one uniform (non-plan) wire dtype:
    the same cast/quantize math in pure numpy.  The stream sender encodes
    each slot with this — per-slot jnp dispatch cost the chunked path as
    much as the whole monolithic encode, erasing the pipeline win.  The
    per-layer reductions, ``round``-half-even, and float casts are all
    IEEE-identical to the jnp codec on the host backend; bit-parity is
    pinned by the streamed-equals-monolithic tests."""
    wire_dtype = resolve_wire_dtype(wire_dtype)
    if isinstance(wire_dtype, WirePlan):
        raise ValueError("np_encode_wire takes a uniform wire dtype; plan "
                         "wires encode slot-by-slot")
    x = np.asarray(x)
    if wire_dtype in _SCALED_WIRES:
        qmax = np.float32(127.0 if wire_dtype == "int8" else 7.0)
        absmax = np.max(np.abs(x), axis=tuple(range(1, x.ndim)),
                        keepdims=True)
        scale = (np.maximum(absmax, np.float32(1e-8)) / qmax) \
            .astype(np.float32)
        q = np.clip(np.round(x / scale), -qmax, qmax).astype(np.int8)
        data = q if wire_dtype == "int8" else _pack_int4(q)
        return (data, scale), data.nbytes + scale.nbytes
    wire = x.astype(_WIRE_DTYPES[wire_dtype])
    return (wire,), wire.nbytes


def np_decode_wire(wire, wire_dtype, dtype) -> np.ndarray:
    """Host-side ``decode_wire`` for one uniform (non-plan) wire dtype:
    identical cast/dequant math in pure numpy.  The streaming assembler
    decodes every bounded chunk with this — a jnp dispatch + host sync
    per 64 KB chunk made the receiver the pipeline bottleneck (streamed
    transfers ran slower than monolithic).  Bit-parity with
    ``decode_wire`` is pinned by the streamed-equals-monolithic tests;
    the two must not drift."""
    wire_dtype = resolve_wire_dtype(wire_dtype)
    if isinstance(wire_dtype, WirePlan):
        raise ValueError("np_decode_wire takes a uniform wire dtype; plan "
                         "wires decode slot-by-slot")
    dtype = np.dtype(_WIRE_DTYPES.get(dtype, dtype)
                     if isinstance(dtype, str) else dtype)
    if wire_dtype == "int8":
        q, s = wire
        return (np.asarray(q).astype(np.float32)
                * np.asarray(s, np.float32)).astype(dtype)
    if wire_dtype == "int4":
        p, s = wire
        p = np.asarray(p, np.uint8)
        lo = (p & 0x0F).astype(np.int8)
        hi = ((p >> 4) & 0x0F).astype(np.int8)
        sx = lambda v: np.where(v > 7, v - 16, v).astype(np.int8)
        q = np.stack([sx(lo), sx(hi)], axis=-1) \
            .reshape(p.shape[:-1] + (p.shape[-1] * 2,))
        return (q.astype(np.float32)
                * np.asarray(s, np.float32)).astype(dtype)
    return np.asarray(wire[0]).astype(dtype)


def _decode_wire_plan(wire, plan: WirePlan, dtype) -> jnp.ndarray:
    if not len(plan):
        return jnp.asarray(wire[0]).astype(dtype)
    it = iter(wire)
    out = None
    for dt, slots in plan.groups():
        arrs = ((next(it), next(it)) if dt in _SCALED_WIRES
                else (next(it),))
        part = decode_wire(arrs, dt, dtype)
        if out is None:
            out = jnp.zeros((len(plan),) + part.shape[1:], dtype)
        out = out.at[np.asarray(slots)].set(part)
    return out


def device_wire_roundtrip(x, wire_dtype, dtype) -> jnp.ndarray:
    """``decode_wire(encode_wire(x))`` without ever leaving the device: the
    same cast/quantize math as the codec above, but no ``np.asarray`` host
    sync.  The async paged path builds its receiver view with this while
    the content hashing (which MUST read host bytes) is parked for later —
    bit-parity with a pool-materialized view is asserted in tests, so the
    two implementations cannot drift apart silently."""
    x = jnp.asarray(x)
    wire_dtype = resolve_wire_dtype(wire_dtype)
    if isinstance(wire_dtype, WirePlan):
        if not len(wire_dtype):
            return x.astype(jnp.float16).astype(dtype)
        out = jnp.zeros(x.shape, dtype)
        for dt, slots in wire_dtype.groups():
            idx = np.asarray(slots)
            out = out.at[idx].set(device_wire_roundtrip(x[idx], dt, dtype))
        return out
    if wire_dtype == "int8":
        absmax = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)),
                         keepdims=True)
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return (q.astype(jnp.float32)
                * scale.astype(jnp.float32)).astype(dtype)
    if wire_dtype == "int4":
        scale = _int4_scale(x)
        q = jnp.clip(jnp.round(x / scale), -7, 7).astype(jnp.int8)
        # nibble packing is a bit-layout transform — it cannot change the
        # quantized values, so the device roundtrip skips it and stays
        # bit-par with the host pack→unpack→dequant path
        return (q.astype(jnp.float32)
                * scale.astype(jnp.float32)).astype(dtype)
    return x.astype(_WIRE_DTYPES[wire_dtype]).astype(dtype)


def roundtrip_kv(payload, wire_dtype: str, dtype):
    """Wire-cast a gathered {"k","v"} payload and decode it back at the
    compute dtype; returns (receiver payload, counted bytes). The ONE
    codec loop both the homogeneous and mapped send paths go through —
    a codec change cannot diverge their accounting."""
    out, n = {}, 0
    for part in ("k", "v"):
        with spans.span(spans.WIRE_ENCODE):
            wire, nb = encode_wire(payload[part], wire_dtype)
        n += nb
        with spans.span(spans.WIRE_DECODE):
            out[part] = decode_wire(wire, wire_dtype, dtype)
    return out, n


def roundtrip_states(states, state_select, wire_dtype):
    """Wire-cast the selected SSM state layers; returns the receiver
    view (non-selected layers zeroed) and the counted bytes.  Under a
    ``WirePlan`` states travel at the plan's finest tier (state stacks
    span the full depth — a per-selected-slot plan does not index them)."""
    if states is None or state_select is None:
        return states, 0
    wd = state_wire_dtype(wire_dtype)
    sel = np.nonzero(np.asarray(state_select))[0]
    counted = [0]

    def roundtrip(x):
        wire, n = encode_wire(jnp.asarray(x)[sel], wd)
        counted[0] += n
        dense = jnp.zeros_like(x)
        return dense.at[sel].set(decode_wire(wire, wd, x.dtype))

    return jax.tree.map(roundtrip, states), counted[0]


def selected_count(select) -> int:
    """Host-side static count of selected layers (0 for a None mask)."""
    if select is None:
        return 0
    return int(np.asarray(select).sum())


def payload_bytes(kv, select, states=None, state_select=None,
                  itemsize: Optional[int] = None) -> int:
    """Analytic wire bytes of the selected subset of a KV stack (+ states).

    ``itemsize`` overrides the KV dtype's itemsize (e.g. 2 for an fp16 wire
    regardless of the compute dtype).
    """
    n = 0
    if kv is not None:
        m = selected_count(select)
        _, B, Sc, Hkv, Dh = kv["k"].shape
        isz = itemsize if itemsize is not None else kv["k"].dtype.itemsize
        n += 2 * m * B * Sc * Hkv * Dh * isz
    if states is not None and state_select is not None:
        m = selected_count(state_select)
        n_layers = jax.tree.leaves(states)[0].shape[0]
        total = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(states))
        n += int(total * m / max(n_layers, 1))
    return n


def assignment_bytes(kv, assignment: LayerAssignment,
                     itemsize: Optional[int] = None) -> int:
    """Analytic wire bytes of a mapped (heterogeneous) KV transfer: exactly
    the P assigned layer pairs cross — receiver-consumable accounting, even
    when the sender originally selected more (M_sender > P)."""
    if kv is None or assignment.num_pairs == 0:
        return 0
    _, B, Sc, Hkv, Dh = kv["k"].shape
    isz = itemsize if itemsize is not None else kv["k"].dtype.itemsize
    return 2 * assignment.num_pairs * B * Sc * Hkv * Dh * isz


class Transport(abc.ABC):
    """A byte-accounted link M_s -> M_r. Subclasses define what physically
    crosses and how it is counted; the log format and per-transfer latency
    stamping are shared.

    Latency stamping and the serving hot path: a synced stamp
    (``sync=True``) calls ``block_until_ready`` on the produced view —
    exact per-transfer device time, but it serializes the host against the
    device and thereby kills the overlap an async scheduler builds
    (sender-side export/gather/wire-cast enqueue while the receiver is
    mid-decode). ``sync=False`` returns the un-synced view immediately and
    parks the record on a deferred-stamp log; ``flush_latency()`` (or the
    next synced send) settles it. Deferred stamps measure enqueue->drain
    wall clock — an overlap-inclusive upper bound, fine for accounting;
    benchmarks that need the true isolated transfer cost keep
    ``sync=True`` (the constructor default)."""

    def __init__(self, packed: bool = True, sync: bool = True,
                 store=None) -> None:
        self.log: List[TransferRecord] = []
        self.packed = packed
        self.sync = sync
        # deferred-stamp log: (record, t0, un-synced receiver view)
        self._pending: List[tuple] = []
        # paged prefix store (repro.store.PageStore): when attached, every
        # KV send routes through the content-addressed paged path — the
        # payload is split into fixed-size pages, only the pages the
        # store's pool is missing are counted as moved, and the record
        # carries the pages_total/pages_sent/pages_hit dedup breakdown
        self.store = store
        # the last send's BlockTable, held PINNED in the store until the
        # next paged send (or release_table) — the serving scheduler
        # gathers admission prefixes from it (via the settling property
        # below; _last_table is the raw slot)
        self._last_table = None
        # deferred paged ingests parked by async sends: (thunk, payload).
        # The thunk runs split_payload's hashing + the pool ingest — the
        # ONE host-syncing stage of a paged send — at flush/poll/first-use
        # instead of inside send(); the payload rides along so poll can
        # check device readiness without blocking.
        self._pending_ingest: List[tuple] = []

    @property
    def last_table(self) -> Optional[Any]:
        """The last paged send's (pinned) BlockTable.  Reading it settles
        any deferred paged ingests first — "first use" of the table IS the
        point an async ``send(sync=False)`` must land in the pool."""
        self._settle_ingests()
        return self._last_table

    @last_table.setter
    def last_table(self, table) -> None:
        self._last_table = table

    def _settle_ingests(self) -> int:
        """Run every deferred paged ingest (in send order — pool dedup and
        table swaps are order-sensitive). Returns the number settled."""
        n = len(self._pending_ingest)
        while self._pending_ingest:
            thunk, _ = self._pending_ingest.pop(0)
            thunk()
        return n

    def attach_store(self, store) -> None:
        """Attach (or replace) the paged prefix store; subsequent sends
        route through it."""
        self.release_table()
        self.store = store

    def release_table(self) -> None:
        """Unpin the last paged send's block table (its pages become
        evictable again)."""
        self._settle_ingests()
        if self._last_table is not None and self.store is not None:
            self.store.release(self._last_table)
        self._last_table = None

    def _swap_table(self, table) -> None:
        prev, self._last_table = self._last_table, table
        if prev is not None:
            self.store.release(prev)

    @property
    def total_bytes(self) -> int:
        return sum(r.n_bytes for r in self.log)

    @property
    def last(self) -> TransferRecord:
        return self.log[-1]

    def flush_latency(self) -> int:
        """Settle every deferred stamp: run parked paged ingests, block on
        the parked views, and write each record's ``latency_s``
        (enqueue->drain wall clock). Returns the number of records
        stamped."""
        self._settle_ingests()
        n = len(self._pending)
        for rec, t0, shared in self._pending:
            jax.block_until_ready(shared)
            rec.latency_s = time.perf_counter() - t0
        self._pending.clear()
        return n

    def _drained(self, tree) -> bool:
        return all(x.is_ready() for x in jax.tree.leaves(tree)
                   if hasattr(x, "is_ready"))

    def poll_latency(self) -> int:
        """Non-blocking ``flush_latency``: stamp (and release) only the
        deferred records whose transfers have already drained, and run
        deferred paged ingests whose payloads are already on host-readable
        device memory (longest-ready prefix only — pool ordering). The
        serving scheduler calls this once per iteration so the pending log
        — which pins each transfer's receiver-side view on device — stays
        bounded by the transfers genuinely in flight, not by the stream
        length. Returns the number of records stamped."""
        while self._pending_ingest \
                and self._drained(self._pending_ingest[0][1]):
            thunk, _ = self._pending_ingest.pop(0)
            thunk()
        still = []
        n = 0
        for rec, t0, shared in self._pending:
            if self._drained(shared):
                rec.latency_s = time.perf_counter() - t0
                n += 1
            else:
                still.append((rec, t0, shared))
        self._pending = still
        return n

    def send(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv, select,
             states=None, state_select=None,
             assignment: Optional[LayerAssignment] = None,
             sync: Optional[bool] = None) -> SharedKV:
        """Move the selected KV (and states) across; return the receiver-side
        view and record a latency-stamped TransferRecord.

        ``assignment`` switches on the heterogeneous path: the wire carries
        the assignment's sender layers (``src``, possibly fewer than the
        sender selected — a mapping policy may drop layers, and only what
        the receiver will consume crosses) and the view is keyed by its
        receiver slots (``dst``). The record's ``layers`` is the mapped
        pair count, so byte accounting tracks M_receiver, not M_sender.

        ``sync`` overrides the transport-level default: True blocks for an
        exact device-synced stamp (the hot-path serializer this flag
        exists to avoid); False/None-with-async-default defers the stamp
        to ``flush_latency``.
        """
        do_sync = self.sync if sync is None else sync
        if do_sync:
            # settle older deferred stamps first — BEFORE this transfer's
            # timer starts, so their drain time cannot inflate it
            self.flush_latency()
        t0 = time.perf_counter()
        if self.store is not None and kv is not None:
            # async in-process paged sends defer the host-syncing hashing
            # (true sync=False); the remote override and the states-carrying
            # path keep the eager ingest (their wires/codecs read bytes
            # inherently)
            if (not do_sync and states is None
                    and type(self)._send_paged is Transport._send_paged):
                shared = self._send_paged_deferred(cfg, kvcfg, kv, select,
                                                   assignment)
            else:
                shared = self._send_paged(cfg, kvcfg, kv, select, states,
                                          state_select, assignment)
        elif assignment is not None:
            shared = self._send_mapped(cfg, kvcfg, kv, assignment,
                                       states, state_select)
        else:
            shared = self._send(cfg, kvcfg, kv, select, states, state_select)
        if do_sync:
            # wall clock around async JAX dispatch measures enqueue, not
            # compute: sync the produced view before stopping the timer
            jax.block_until_ready(shared)
            self.log[-1].latency_s = time.perf_counter() - t0
        else:
            # keep the serving pipeline rolling: stamp off the critical
            # path when the caller (or a benchmark) next flushes
            self._pending.append((self.log[-1], t0, shared))
        return shared

    @abc.abstractmethod
    def _send(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv, select,
              states=None, state_select=None) -> SharedKV:
        """Transport-specific transfer; must append a TransferRecord."""

    def _send_mapped(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv,
                     assignment: LayerAssignment, states=None,
                     state_select=None) -> SharedKV:
        """Heterogeneous transfer under a ``LayerAssignment``; must append
        a TransferRecord whose ``layers`` is the mapped pair count.
        Concrete default (not abstract) so pre-existing Transport
        subclasses that only implement ``_send`` keep instantiating; they
        simply cannot serve the hetero path until they override this."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support mapped "
            "(heterogeneous) transfers; override _send_mapped")

    # -- the paged (content-addressed) path --------------------------------
    def _paged_wire_dtype(self, kv):
        """The wire dtype (possibly a ``WirePlan``) the store hashes/pages
        at.  Transports with an explicit wire dtype use it; the in-memory
        hand-over pages at the model's own dtype (a lossless cast), falling
        back to fp32 when the compute dtype has no wire form."""
        wd = getattr(self, "wire_dtype", None)
        if wd is not None:
            return wd
        name = np.dtype(kv["k"].dtype).name
        return name if name in _WIRE_DTYPES else "float32"

    def _paged_states(self, states, state_select):
        """States ride ALONGSIDE the paged KV (sequence-axis paging does
        not apply to fixed-size SSM state): wire-dtype transports
        round-trip them through the codec, the in-memory hand-over passes
        them through at analytic bytes."""
        wd = getattr(self, "wire_dtype", None)
        if wd is None:
            return states, payload_bytes(None, None, states, state_select)
        return roundtrip_states(states, state_select, wd)

    def _send_paged(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv,
                    select, states=None, state_select=None,
                    assignment: Optional[LayerAssignment] = None
                    ) -> SharedKV:
        """The store-routed transfer shared by the in-process transports:
        gather the selected (or assignment-mapped) payload, ingest it into
        the attached ``PageStore`` (dedup against the pool happens there),
        and materialize the receiver view back out of the pool — so what
        the receiver consumes is, by construction, what the pages hold.
        Counted bytes are the NOVEL pages only (plus int8 scales and
        states): the dedup win the record's pages_* fields break down.
        ``RemoteTransport`` overrides this with the framed
        page_query/page_need/page_data exchange."""
        self._settle_ingests()   # older async ingests land first (ordering)
        if assignment is not None:
            payload = gather_mapped(kv, assignment)
            layers = tuple(assignment.dst)
            src_layers = tuple(assignment.src)
            sel_mask = np.asarray(assignment.dst_mask())
            layer_count = assignment.num_pairs
        else:
            payload = gather_selected(kv, jnp.asarray(select))
            layers = selected_layer_ids(select)
            src_layers = None
            sel_mask = np.asarray(select)
            layer_count = selected_count(select)
        wd = self._paged_wire_dtype(kv)
        with spans.span(spans.WIRE_ENCODE):
            table, novel, novel_bytes = self.store.ingest(
                payload, layers=layers, select=sel_mask, wire_dtype=wd,
                pos_mode=kvcfg.pos_mode, src_layers=src_layers)
        # ingest pinned the table; release on any failure before the swap
        # so an aborted send cannot leak refcounts into the pool
        try:
            rx_states, state_bytes = self._paged_states(states,
                                                        state_select)
            with spans.span(spans.WIRE_DECODE):
                shared = self.store.materialize(table, states=rx_states,
                                                state_select=state_select)
            if not self.packed:
                shared = shared.to_dense()
            self._swap_table(table)
        except BaseException:
            self.store.release(table)
            raise
        self.log.append(TransferRecord(
            kind="kv", n_bytes=novel_bytes + table.scale_nbytes
            + state_bytes,
            layers=layer_count, context_len=table.prefix_len,
            wire_dtype=self._wire_spec(),
            pages_total=table.num_pages, pages_sent=len(novel),
            pages_hit=table.num_pages - len(novel)))
        return shared

    def _send_paged_deferred(self, cfg: ModelConfig, kvcfg: KVCommConfig,
                             kv, select,
                             assignment: Optional[LayerAssignment] = None
                             ) -> SharedKV:
        """True ``sync=False`` paged send: nothing in here reads device
        bytes on the host.  The receiver view is built from a device-only
        codec roundtrip (``device_wire_roundtrip`` — bit-identical to what
        ``PageStore.materialize`` would rebuild from the pool), while the
        content hashing + pool ingest — the host-syncing stage — is parked
        as a thunk that ``flush_latency()`` / ``poll_latency()`` / the
        first read of ``last_table`` runs, mirroring deferred latency
        stamping.  The TransferRecord is appended immediately with zeroed
        page stats; the thunk fills them in when the ingest lands."""
        self._settle_ingests()
        if assignment is not None:
            payload = gather_mapped(kv, assignment)
            layers = tuple(assignment.dst)
            src_layers = tuple(assignment.src)
            sel_mask = np.asarray(assignment.dst_mask())
            layer_count = assignment.num_pairs
        else:
            payload = gather_selected(kv, jnp.asarray(select))
            layers = selected_layer_ids(select)
            src_layers = None
            sel_mask = np.asarray(select)
            layer_count = selected_count(select)
        wd = self._paged_wire_dtype(kv)
        dtype = kv["k"].dtype
        prefix_len = int(kv["k"].shape[2])
        rx_payload = {part: device_wire_roundtrip(payload[part], wd, dtype)
                      for part in ("k", "v")}
        if assignment is not None:
            shared = build_mapped(kvcfg, rx_payload, assignment, prefix_len)
        else:
            shared = build_packed(kvcfg, rx_payload, layers, prefix_len,
                                  select=jnp.asarray(sel_mask))
        if not self.packed:
            shared = shared.to_dense()
        rec = TransferRecord(
            kind="kv", n_bytes=0, layers=layer_count,
            context_len=prefix_len, wire_dtype=self._wire_spec())
        self.log.append(rec)

        def ingest():
            table, novel, novel_bytes = self.store.ingest(
                payload, layers=layers, select=sel_mask, wire_dtype=wd,
                pos_mode=kvcfg.pos_mode, src_layers=src_layers)
            try:
                self._swap_table(table)
            except BaseException:
                self.store.release(table)
                raise
            rec.n_bytes = novel_bytes + table.scale_nbytes
            rec.pages_total = table.num_pages
            rec.pages_sent = len(novel)
            rec.pages_hit = table.num_pages - len(novel)

        self._pending_ingest.append((ingest, payload))
        return shared

    def send_text(self, token_count: int, bytes_per_token: int = 2) -> int:
        """Account an NLD/CIPHER-style natural-language transfer."""
        n = token_count * bytes_per_token
        self.log.append(TransferRecord("text", n, 0, token_count))
        return n

    def send_hidden(self, batch: int, d_model: int, itemsize: int = 2) -> int:
        """Account an activation-communication transfer (one d-vector per
        sample, Ramesh & Li 2025)."""
        n = batch * d_model * itemsize
        self.log.append(TransferRecord("hidden", n, 1, 1))
        return n

    def _wire_spec(self) -> str:
        """The record-friendly string form of this transport's wire dtype
        ("model" for the dtype-less in-memory hand-over)."""
        wd = getattr(self, "wire_dtype", None)
        return "model" if wd is None else wire_spec(wd)

    def _record_kv(self, n_bytes: int, select, prefix_len: int,
                   wire_dtype: str) -> None:
        self.log.append(TransferRecord(
            kind="kv", n_bytes=n_bytes, layers=selected_count(select),
            context_len=prefix_len, wire_dtype=wire_dtype))


class InMemoryTransport(Transport):
    """In-process hand-over: the receiver reads the sender's device buffers
    (packed mode gathers the M selected layers first; dense mode is a pure
    zero-copy view).  Nothing crosses a wire, so bytes are the analytic
    payload size of the selected layers at the KV's own dtype (identical to
    what a lossless wire at that dtype would move)."""

    def _send(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv, select,
              states=None, state_select=None) -> SharedKV:
        build = pack_shared if self.packed else build_shared
        shared = build(kvcfg, kv, select, states, state_select)
        n = payload_bytes(kv, select, states, state_select)
        self._record_kv(n, select, shared.prefix_len, wire_dtype="model")
        return shared

    def _send_mapped(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv,
                     assignment: LayerAssignment, states=None,
                     state_select=None) -> SharedKV:
        if kv is None:
            shared = build_shared(kvcfg, None,
                                  jnp.asarray(assignment.dst_mask()),
                                  states, state_select)
            n = payload_bytes(None, None, states, state_select)
        else:
            if self.packed:
                shared = pack_mapped(kvcfg, kv, assignment, states,
                                     state_select)
            else:
                shared = scatter_mapped(kvcfg, gather_mapped(kv, assignment),
                                        assignment, int(kv["k"].shape[2]),
                                        states, state_select)
            n = assignment_bytes(kv, assignment) \
                + payload_bytes(None, None, states, state_select)
        self.log.append(TransferRecord(
            kind="kv", n_bytes=n, layers=assignment.num_pairs,
            context_len=shared.prefix_len, wire_dtype="model"))
        return shared


class SerializedTransport(Transport):
    """Materializes the actual wire payload and counts its bytes.

    The selected layers' KV is gathered along the layer axis, cast to
    ``wire_dtype``, counted via ``nbytes``, and decoded back at the compute
    dtype.  In packed mode (default) the decoded (M, ...) payload plus its
    static layer map IS the receiver-side view; in dense mode it is
    scattered back into a zero-padded (L, ...) stack (non-selected layers
    are zeros — masked out by ``select`` on the receiver), so either
    round-trip is exact modulo the wire cast.

    ``wire_dtype``: "float16" (default) | "bfloat16" | "float32" | "int8"
    | "int4" | a ``WirePlan`` (or its "plan:..." spec) for adaptive
    per-layer precision.  int8/int4 use per-layer symmetric quantization;
    the fp32 scales are counted as part of the payload.
    """

    def __init__(self, wire_dtype="float16",
                 packed: bool = True, sync: bool = True,
                 store=None) -> None:
        super().__init__(packed=packed, sync=sync, store=store)
        self.wire_dtype = resolve_wire_dtype(wire_dtype)

    # -- wire codec (module-level functions, shared with RemoteTransport) --
    def _roundtrip_kv(self, payload, dtype):
        return roundtrip_kv(payload, self.wire_dtype, dtype)

    def _roundtrip_states(self, states, state_select):
        return roundtrip_states(states, state_select, self.wire_dtype)

    # -- transport ---------------------------------------------------------
    def _send(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv, select,
              states=None, state_select=None) -> SharedKV:
        n_bytes = 0
        rx_payload = None
        layers = selected_layer_ids(select)
        prefix_len = 0
        if kv is not None:
            prefix_len = int(kv["k"].shape[2])
            payload = gather_selected(kv, jnp.asarray(select))
            rx_payload, n_bytes = self._roundtrip_kv(payload,
                                                     kv["k"].dtype)
        rx_states, state_bytes = self._roundtrip_states(states, state_select)
        n_bytes += state_bytes
        if kv is None:
            shared = build_shared(kvcfg, None, select, rx_states,
                                  state_select)
        elif self.packed:
            shared = build_packed(kvcfg, rx_payload, layers, prefix_len,
                                  select=select, states=rx_states,
                                  state_select=state_select)
        else:
            idx = np.asarray(layers, np.int32)
            rx_kv = {}
            for part in ("k", "v"):
                dense = jnp.zeros_like(kv[part])
                rx_kv[part] = dense.at[idx].set(rx_payload[part])
            shared = build_shared(kvcfg, rx_kv, select, rx_states,
                                  state_select)
        self._record_kv(n_bytes, select, shared.prefix_len,
                        wire_dtype=self._wire_spec())
        return shared

    def _send_mapped(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv,
                     assignment: LayerAssignment, states=None,
                     state_select=None) -> SharedKV:
        n_bytes = 0
        rx_payload = None
        prefix_len = 0
        if kv is not None:
            prefix_len = int(kv["k"].shape[2])
            payload = gather_mapped(kv, assignment)
            rx_payload, n_bytes = self._roundtrip_kv(payload,
                                                     kv["k"].dtype)
        rx_states, state_bytes = self._roundtrip_states(states, state_select)
        n_bytes += state_bytes
        if kv is None:
            shared = build_shared(kvcfg, None,
                                  jnp.asarray(assignment.dst_mask()),
                                  rx_states, state_select)
        elif self.packed:
            shared = build_mapped(kvcfg, rx_payload, assignment, prefix_len,
                                  states=rx_states,
                                  state_select=state_select)
        else:
            shared = scatter_mapped(kvcfg, rx_payload, assignment,
                                    prefix_len, states=rx_states,
                                    state_select=state_select)
        self.log.append(TransferRecord(
            kind="kv", n_bytes=n_bytes, layers=assignment.num_pairs,
            context_len=prefix_len, wire_dtype=self._wire_spec()))
        return shared
