"""RemoteTransport: cross-process KV shipping over a framed wire codec.

Every transport before this one lives in a single process — even
``SerializedTransport`` only materializes the wire payload to count it.
This module makes the byte accounting mean something physical: the gathered
selected-layer payload (the same gather/cast half ``SerializedTransport``
uses — ``repro.comm.transport.encode_wire``/``decode_wire``, so the codec
and its accounting can never diverge) is packed into a length-prefixed,
versioned, checksummed frame and shipped through a pluggable byte channel:

  LoopbackChannel — an in-process byte buffer: the frame is really encoded,
                    really framed, really decoded, without a second process
                    (what the conformance tests and the serving scheduler's
                    remote row run on).
  SocketChannel   — a connected TCP stream (the two-process path:
                    ``repro.launch.remote_serve`` / ``examples/remote_pair``).
  FileChannel     — shared-filesystem staging: frames land as numbered chunk
                    files (atomic rename), the reader tails them in order
                    (LMCache-style disaggregated KV residency without a
                    network hop).

Frame layout (all integers big-endian)::

  offset  size  field
  0       4     magic  b"KVCM"
  4       2     protocol version (currently 1)
  6       4     header length H
  10      8     payload length P
  18      4     CRC-32 over header + payload
  22      H     header: UTF-8 JSON {kind, meta, arrays:[{name,dtype,shape}]}
  22+H    P     payload: the arrays' raw bytes, concatenated in header order

Decoding is defensive end to end: every malformed input raises a typed
``RemoteProtocolError`` subclass (truncated stream, bad magic, version skew,
checksum mismatch, dtype/shape inconsistencies) — a corrupted frame can
never silently become garbage KV.  The fault-injection suite
(``tests/test_remote.py``) property-tests this over random frame mutations.

The receiver-side view is a packed RECEIVER-keyed ``SharedKV`` (incl. a
heterogeneous ``LayerAssignment``'s dst slots and ``src_layers``
provenance), so the selection-specialized fast path and the serving
scheduler consume a remote transfer unchanged.
"""
from __future__ import annotations

import abc
import json
import os
import socket
import struct
import time
import zlib
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.channel import TransferRecord
from repro.core.layermap import LayerAssignment
from repro.core.protocol import (gather_mapped, gather_selected,
                                 selected_layer_ids)
from repro.core.types import KVCommConfig, SharedKV
from repro.utils import spans
from repro.comm.transport import (Transport, WirePlan, as_wire_plan,
                                  decode_wire, encode_wire, np_decode_wire,
                                  np_encode_wire,
                                  resolve_wire_dtype, selected_count,
                                  state_wire_dtype, wire_has_scales,
                                  wire_spec)

PROTOCOL_VERSION = 1
MAGIC = b"KVCM"
_PREFIX = struct.Struct(">4sHIQI")        # magic, version, hdr len, body len, crc
MAX_HEADER_BYTES = 1 << 26                # 64 MiB of JSON is never legitimate
MAX_BODY_BYTES = 1 << 32                  # a corrupted length prefix must be
                                          # rejected up front, not discovered
                                          # after buffering the claim


# ---------------------------------------------------------------------------
# typed protocol errors
# ---------------------------------------------------------------------------
class RemoteProtocolError(RuntimeError):
    """Base for every failure of the remote framing/decoding protocol."""


class ChannelClosedError(RemoteProtocolError):
    """The channel ended cleanly at a frame boundary (peer hung up)."""


class ChannelTimeoutError(ChannelClosedError):
    """The channel produced nothing within its deadline — distinguishable
    from a genuine peer close (a stalled peer may still be alive, so a
    retry policy treats this as retriable).  Subclasses
    ``ChannelClosedError`` so pre-existing clean-close handling (server
    loops, boundary tests) keeps working unchanged."""


class FrameTruncatedError(RemoteProtocolError):
    """The channel ended mid-frame — a disconnect or a cut-short stream."""


class HeaderCorruptError(RemoteProtocolError):
    """Bad magic, implausible lengths, or an unparsable header document."""


class VersionSkewError(RemoteProtocolError):
    """The peer speaks a different protocol version."""


class FrameCorruptError(RemoteProtocolError):
    """Checksum mismatch: the frame's bytes were altered in flight."""


class PayloadMismatchError(RemoteProtocolError):
    """The header's dtype/shape claims are inconsistent with the payload
    (or with each other) — the frame cannot describe a coherent transfer."""


# ---------------------------------------------------------------------------
# channels
# ---------------------------------------------------------------------------
class RemoteChannel(abc.ABC):
    """A byte-stream channel.  ``read`` returns up to ``n`` bytes and b""
    once the stream is exhausted/closed (the framing layer turns a b"" at a
    frame boundary into ``ChannelClosedError`` and mid-frame into
    ``FrameTruncatedError``)."""

    @abc.abstractmethod
    def write(self, data: bytes) -> None: ...

    @abc.abstractmethod
    def read(self, n: int) -> bytes: ...

    def close(self) -> None:
        pass

    # Whole-frame deadline hooks: the framing layer calls ``begin_frame``
    # once a frame's first bytes have arrived and ``end_frame`` when the
    # frame is fully read (or failed).  Default is a no-op; channels with a
    # wall-clock budget (SocketChannel) arm a deadline here so a peer
    # trickling one byte per io-timeout window cannot hold a read open
    # forever.
    def begin_frame(self) -> None:
        pass

    def end_frame(self) -> None:
        pass


class LoopbackChannel(RemoteChannel):
    """In-process byte buffer: writes append, reads consume from the front.
    The frame still crosses the full encode -> bytes -> decode path — only
    the process boundary is elided."""

    def __init__(self) -> None:
        self._buf = bytearray()
        self._closed = False

    def write(self, data: bytes) -> None:
        if self._closed:
            raise ChannelClosedError("write on a closed LoopbackChannel")
        self._buf.extend(data)

    def read(self, n: int) -> bytes:
        chunk = bytes(self._buf[:n])
        del self._buf[:len(chunk)]
        return chunk

    def close(self) -> None:
        self._closed = True

    def __len__(self) -> int:
        return len(self._buf)


class SocketChannel(RemoteChannel):
    """A connected TCP stream.  Build one from an accepted/connected socket,
    or dial with ``SocketChannel.connect`` (retries until the server's
    listener is up — the two-process launch race)."""

    def __init__(self, sock: socket.socket,
                 frame_timeout_s: Optional[float] = None) -> None:
        self.sock = sock
        # per-recv socket timeout as configured at connect/accept time
        self.io_timeout_s = sock.gettimeout()
        # whole-frame budget: from a frame's FIRST byte, the rest must
        # arrive within this window — a trickling peer (1 byte per
        # io-timeout) can no longer hold a frame read open forever.
        # Defaults to the io timeout; None (blocking socket, no override)
        # keeps the legacy unbounded behavior.
        self.frame_timeout_s = (frame_timeout_s if frame_timeout_s
                                is not None else self.io_timeout_s)
        self._deadline: Optional[float] = None

    @classmethod
    def connect(cls, host: str, port: int, timeout_s: float = 30.0,
                retry_s: float = 0.1,
                io_timeout_s: Optional[float] = None) -> "SocketChannel":
        """Dial with a REAL deadline: each connect attempt's own timeout is
        capped at the remaining budget (never a hardcoded inner timeout
        that could outlive ``timeout_s``).  ``io_timeout_s`` arms a
        per-read/write socket timeout on the connected channel (stalled
        peers surface as ``ChannelTimeoutError`` instead of hanging)."""
        deadline = time.monotonic() + timeout_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise ChannelTimeoutError(
                    f"could not connect to {host}:{port} "
                    f"within {timeout_s}s")
            try:
                sock = socket.create_connection(
                    (host, port), timeout=max(remaining, 1e-3))
                sock.settimeout(io_timeout_s)
                return cls(sock)
            except OSError as e:
                if time.monotonic() >= deadline:
                    raise ChannelClosedError(
                        f"could not connect to {host}:{port}: {e}") from e
                time.sleep(min(retry_s,
                               max(deadline - time.monotonic(), 0.0)))

    def write(self, data: bytes) -> None:
        try:
            self.sock.sendall(data)
        except socket.timeout as e:
            raise ChannelTimeoutError(f"socket send timed out: {e}") from e
        except OSError as e:
            raise ChannelClosedError(f"socket send failed: {e}") from e

    def begin_frame(self) -> None:
        if self.frame_timeout_s is not None:
            self._deadline = time.monotonic() + self.frame_timeout_s

    def end_frame(self) -> None:
        self._deadline = None
        try:
            self.sock.settimeout(self.io_timeout_s)
        except OSError:
            pass

    def read(self, n: int) -> bytes:
        if self._deadline is not None:
            remaining = self._deadline - time.monotonic()
            if remaining <= 0:
                raise ChannelTimeoutError(
                    f"frame not complete within the {self.frame_timeout_s}s"
                    " whole-frame deadline (peer trickling or stalled)")
            # cap THIS recv's wait by the remaining frame budget, so slow
            # drips make progress against the deadline instead of each
            # enjoying a fresh io timeout
            try:
                self.sock.settimeout(
                    remaining if self.io_timeout_s is None
                    else min(self.io_timeout_s, remaining))
            except OSError as e:
                raise ChannelClosedError(
                    f"socket settimeout failed: {e}") from e
        try:
            return self.sock.recv(min(n, 1 << 20))
        except socket.timeout as e:
            raise ChannelTimeoutError(f"socket recv timed out: {e}") from e
        except OSError as e:
            raise ChannelClosedError(f"socket recv failed: {e}") from e

    def close(self) -> None:
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()


class FileChannel(RemoteChannel):
    """Shared-filesystem staging: every ``write`` lands one numbered chunk
    file (written to a temp name, then atomically renamed so a reader never
    sees a half-written chunk); ``read`` tails the chunk sequence in order,
    polling up to ``timeout_s`` for the next chunk to appear.  Two processes
    sharing a directory get a one-way channel; consumed chunks are unlinked
    after the read so staging space stays bounded.

    Chunk names are namespaced by a per-connection NONCE: the writer mints
    one on its first ``write``, publishes it through an atomically-renamed
    ``<name>.nonce`` marker (clearing any stale chunks a dead pair left
    under this channel name), and the reader adopts whatever the marker
    says — re-checking it until its first chunk lands, so a reader that
    raced a writer restart locks onto the NEW stream instead of consuming
    a dead pair's leftovers.  Without the nonce, both sides restarting at
    sequence 0 could silently replay stale chunk files as fresh frames.

    Polling backs off exponentially from ``poll_s`` up to ``max_poll_s``
    (reset on every hit), so an idle reader doesn't spin the filesystem at
    a fixed rate.  A writer's ``close()`` drops an ``.eof`` marker naming
    its final sequence number, which lets the reader tell a CLEAN close
    (marker present, all chunks consumed -> b"" -> ``ChannelClosedError``
    at a frame boundary / ``FrameTruncatedError`` mid-frame) apart from a
    stalled writer (no marker within ``timeout_s`` ->
    ``ChannelTimeoutError``) — previously both surfaced as the same
    timeout-shaped truncation."""

    def __init__(self, directory: str, name: str = "kv",
                 poll_s: float = 0.01, timeout_s: float = 10.0,
                 consume: bool = True, max_poll_s: float = 0.25) -> None:
        self.directory = directory
        self.name = name
        self.poll_s = poll_s
        self.max_poll_s = max(max_poll_s, poll_s)
        self.timeout_s = timeout_s
        self.consume = consume
        os.makedirs(directory, exist_ok=True)
        self._wseq = 0
        self._rseq = 0
        self._rbuf = b""
        self._roff = 0
        self._nonce: Optional[str] = None
        self._published = False        # True once THIS side minted the nonce

    def _marker(self) -> str:
        return os.path.join(self.directory, f"{self.name}.nonce")

    def _eof_marker(self) -> str:
        assert self._nonce is not None
        return os.path.join(self.directory,
                            f"{self.name}.{self._nonce}.eof")

    def _writer_closed(self) -> bool:
        """True when the writer published an EOF marker and every chunk it
        wrote has been consumed — the stream genuinely ended."""
        if self._nonce is None:
            return False
        try:
            with open(self._eof_marker(), "r") as f:
                final_seq = int(f.read().strip() or 0)
        except (OSError, ValueError):
            return False
        return self._rseq >= final_seq

    def _path(self, seq: int) -> str:
        assert self._nonce is not None
        return os.path.join(
            self.directory, f"{self.name}.{self._nonce}.{seq:08d}.chunk")

    def _publish_nonce(self) -> None:
        self._nonce = os.urandom(6).hex()
        self._published = True
        # a fresh writer owns the channel name: clear whatever chunks a
        # dead pair left so a restarted reader can never consume them
        for fn in os.listdir(self.directory):
            if fn.startswith(self.name + ".") \
                    and fn.endswith((".chunk", ".eof")):
                try:
                    os.unlink(os.path.join(self.directory, fn))
                except OSError:
                    pass
        tmp = self._marker() + "." + self._nonce
        with open(tmp, "w") as f:
            f.write(self._nonce)
        os.replace(tmp, self._marker())

    def _adopt_nonce(self) -> None:
        """Reader side: take the nonce the writer's marker advertises.
        Only called before the first chunk has been consumed — after
        that, the stream identity is locked (a mid-stream nonce change is
        a writer restart, surfaced as a timeout -> truncated frame, never
        a silent stream splice)."""
        try:
            with open(self._marker(), "r") as f:
                nonce = f.read().strip()
        except OSError:
            return
        if nonce:
            self._nonce = nonce

    def write(self, data: bytes) -> None:
        if not self._published:
            self._publish_nonce()
        tmp = self._path(self._wseq) + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, self._path(self._wseq))
        self._wseq += 1

    def read(self, n: int) -> bytes:
        if self._roff >= len(self._rbuf):
            deadline = time.monotonic() + self.timeout_s
            pause = self.poll_s
            while True:
                if not self._published and self._rseq == 0:
                    self._adopt_nonce()
                path = (self._path(self._rseq) if self._nonce is not None
                        else None)
                if path is not None and os.path.exists(path):
                    break
                if self._writer_closed():
                    return b""      # clean end: framing decides Closed
                                    # (boundary) vs Truncated (mid-frame)
                if time.monotonic() >= deadline:
                    raise ChannelTimeoutError(
                        f"no chunk {self._rseq} under {self.name!r} "
                        f"within {self.timeout_s}s (writer stalled or "
                        "gone without closing)")
                time.sleep(min(pause, max(
                    deadline - time.monotonic(), 0.0)))
                # capped exponential backoff: idle polls decay to
                # max_poll_s instead of hammering the filesystem
                pause = min(pause * 2.0, self.max_poll_s)
            with open(path, "rb") as f:
                self._rbuf = f.read()
            self._roff = 0
            self._rseq += 1
            if self.consume:
                try:
                    os.unlink(path)
                except OSError:
                    pass
        chunk = self._rbuf[self._roff:self._roff + n]
        self._roff += len(chunk)
        return chunk

    def close(self) -> None:
        """Writer side: publish the EOF marker (atomic rename, like the
        chunks) so the reader can distinguish this clean close from a
        stall.  A reader-side close is a no-op."""
        if not self._published:
            return
        tmp = self._eof_marker() + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(str(self._wseq))
            os.replace(tmp, self._eof_marker())
        except OSError:
            pass


# ---------------------------------------------------------------------------
# the framed codec
# ---------------------------------------------------------------------------
def _np_dtype(name: str) -> np.dtype:
    """Resolve a wire dtype name, including the ml_dtypes extras numpy's
    constructor does not know (bfloat16)."""
    try:
        return np.dtype(name)
    except TypeError:
        try:
            import ml_dtypes
            return np.dtype(getattr(ml_dtypes, name))
        except (ImportError, AttributeError, TypeError):
            raise PayloadMismatchError(
                f"unknown array dtype {name!r} in frame header") from None


def encode_frame(kind: str, meta: Dict[str, Any],
                 arrays: Dict[str, np.ndarray]) -> bytes:
    """Pack one message (a JSON-able ``meta`` dict plus named arrays) into
    the length-prefixed, CRC-protected wire frame."""
    specs, chunks = [], []
    for name, arr in arrays.items():
        a = np.ascontiguousarray(arr)
        specs.append({"name": name, "dtype": a.dtype.name,
                      "shape": list(a.shape)})
        chunks.append(a.tobytes())
    body = b"".join(chunks)
    header = json.dumps({"kind": kind, "meta": meta,
                         "arrays": specs}).encode("utf-8")
    crc = zlib.crc32(body, zlib.crc32(header))
    return _PREFIX.pack(MAGIC, PROTOCOL_VERSION, len(header), len(body),
                        crc) + header + body


def _read_exactly(channel: RemoteChannel, n: int, what: str,
                  got: bytes = b"") -> bytes:
    buf = bytearray(got)
    while len(buf) < n:
        chunk = channel.read(n - len(buf))
        if not chunk:
            raise FrameTruncatedError(
                f"channel ended after {len(buf)}/{n} bytes of {what}")
        buf.extend(chunk)
    return bytes(buf)


def read_frame(channel: RemoteChannel
               ) -> Tuple[str, Dict[str, Any], Dict[str, np.ndarray]]:
    """Read and validate ONE frame off the channel.

    Returns ``(kind, meta, arrays)``.  Raises ``ChannelClosedError`` if the
    stream ends cleanly before the first byte, and a specific
    ``RemoteProtocolError`` subclass for every way a frame can be wrong —
    never a partially-decoded or corrupt result.
    """
    first = channel.read(_PREFIX.size)
    if not first:
        raise ChannelClosedError("channel closed at frame boundary")
    # the frame has started: arm the channel's whole-frame deadline (a
    # no-op on channels without one) — waiting BETWEEN frames stays
    # unbounded, a frame in flight must complete within the budget
    channel.begin_frame()
    try:
        prefix = _read_exactly(channel, _PREFIX.size, "frame prefix",
                               got=first)
        magic, version, hlen, blen, crc = _PREFIX.unpack(prefix)
        if magic != MAGIC:
            raise HeaderCorruptError(f"bad frame magic {magic!r}")
        if version != PROTOCOL_VERSION:
            raise VersionSkewError(
                f"peer speaks protocol v{version}, this side "
                f"v{PROTOCOL_VERSION}")
        if hlen > MAX_HEADER_BYTES or blen > MAX_BODY_BYTES:
            raise HeaderCorruptError(
                f"implausible frame lengths (header {hlen}, payload {blen})")
        header = _read_exactly(channel, hlen, "header")
        body = _read_exactly(channel, blen, "payload")
    finally:
        channel.end_frame()
    if zlib.crc32(body, zlib.crc32(header)) != crc:
        raise FrameCorruptError("frame checksum mismatch")
    try:
        doc = json.loads(header.decode("utf-8"))
        kind, meta, specs = doc["kind"], doc["meta"], doc["arrays"]
        assert isinstance(kind, str) and isinstance(specs, list)
    except (UnicodeDecodeError, ValueError, KeyError, TypeError,
            AssertionError) as e:
        raise HeaderCorruptError(f"unparsable frame header: {e}") from None
    arrays: Dict[str, np.ndarray] = {}
    off = 0
    try:
        for spec in specs:
            dt = _np_dtype(spec["dtype"])
            shape = tuple(int(d) for d in spec["shape"])
            if any(d < 0 for d in shape):
                raise PayloadMismatchError(f"negative dim in shape {shape}")
            count = int(np.prod(shape, dtype=np.int64)) if shape else 1
            nbytes = count * dt.itemsize
            if off + nbytes > len(body):
                raise PayloadMismatchError(
                    f"array {spec['name']!r} claims {nbytes} bytes at "
                    f"offset {off} but the payload holds {len(body)}")
            arrays[spec["name"]] = np.frombuffer(
                body, dt, count, off).reshape(shape)
            off += nbytes
    except (KeyError, TypeError, ValueError, OverflowError) as e:
        raise PayloadMismatchError(
            f"malformed array spec in frame header: {e}") from None
    if off != len(body):
        raise PayloadMismatchError(
            f"payload holds {len(body)} bytes but the header accounts "
            f"for {off}")
    return kind, meta, arrays


# ---------------------------------------------------------------------------
# the health payload (liveness + routing signals)
# ---------------------------------------------------------------------------
# Version 1 carried {"answered", "prefix_installed", "pool"}; version 2 adds
# the routing signals the serving fabric scores replicas by: the pool's
# resident page IDs (prefix-affinity overlap), scheduler queue depth, and
# slot occupancy.  The meta rides an ordinary "health_ack" frame, so the
# FRAME protocol version is untouched — mixed-version fleets never raise
# ``VersionSkew`` over a health probe; ``parse_health_meta`` fills whatever
# keys an older peer omitted with inert defaults.
HEALTH_META_VERSION = 2

HEALTH_DEFAULTS: Dict[str, Any] = {
    "health_version": 1,           # a payload without the field IS v1
    "answered": 0,
    "prefix_installed": False,
    "pool": None,                  # dict of StoreStats fields, or None
    "page_ids": [],                # resident page ids (affinity signal)
    "queue_depth": 0,              # connections + queries waiting/served
    "slots": {"capacity": 0, "occupied": 0},
}


def build_health_meta(*, answered: int, prefix_installed: bool,
                      pool: Optional[Dict[str, Any]] = None,
                      page_ids: Optional[list] = None,
                      queue_depth: int = 0,
                      slots_capacity: int = 0,
                      slots_occupied: int = 0) -> Dict[str, Any]:
    """The v2 health_ack meta a server answers a ``health`` frame with."""
    return {
        "health_version": HEALTH_META_VERSION,
        "answered": int(answered),
        "prefix_installed": bool(prefix_installed),
        "pool": pool,
        "page_ids": list(page_ids) if page_ids is not None else [],
        "queue_depth": int(queue_depth),
        "slots": {"capacity": int(slots_capacity),
                  "occupied": int(slots_occupied)},
    }


def parse_health_meta(meta: Dict[str, Any]) -> Dict[str, Any]:
    """Normalize a health_ack meta of ANY version into the v2 shape.

    Version-tolerant by construction: every key an older (or newer) peer
    does not send falls back to ``HEALTH_DEFAULTS``, and malformed nested
    values degrade to the defaults rather than raising — a router must be
    able to score a mixed-version fleet, not crash on its oldest member."""
    if not isinstance(meta, dict):
        raise PayloadMismatchError(
            f"health_ack meta must be a dict, got {type(meta).__name__}")
    out = dict(HEALTH_DEFAULTS)
    out["slots"] = dict(HEALTH_DEFAULTS["slots"])
    for key in ("health_version", "answered", "queue_depth"):
        try:
            out[key] = int(meta.get(key, out[key]))
        except (TypeError, ValueError):
            pass
    out["prefix_installed"] = bool(meta.get("prefix_installed", False))
    pool = meta.get("pool")
    out["pool"] = pool if isinstance(pool, dict) else None
    page_ids = meta.get("page_ids")
    if isinstance(page_ids, (list, tuple)):
        out["page_ids"] = [str(p) for p in page_ids]
    slots = meta.get("slots")
    if isinstance(slots, dict):
        for key in ("capacity", "occupied"):
            try:
                out["slots"][key] = int(slots.get(key, 0))
            except (TypeError, ValueError):
                pass
    return out


def decode_frame(buf: bytes
                 ) -> Tuple[str, Dict[str, Any], Dict[str, np.ndarray]]:
    """Decode one frame from a contiguous byte string (a convenience over
    ``read_frame`` for staged/stored frames); trailing garbage is an
    error."""
    ch = LoopbackChannel()
    ch.write(buf)
    out = read_frame(ch)
    if len(ch):
        raise PayloadMismatchError(
            f"{len(ch)} trailing bytes after the frame")
    return out


# ---------------------------------------------------------------------------
# state pytrees on the wire (nested dict/list/tuple of arrays)
# ---------------------------------------------------------------------------
def _tree_parts(tree):
    """(JSON skeleton with {"__leaf__": i} markers, [leaves])."""
    leaves = []

    def walk(t):
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        if isinstance(t, (list, tuple)):
            node = [walk(v) for v in t]
            return node if isinstance(t, list) else {"__tuple__": node}
        leaves.append(t)
        return {"__leaf__": len(leaves) - 1}

    return walk(tree), leaves


def _tree_build(skel, leaves):
    if isinstance(skel, dict):
        if set(skel) == {"__leaf__"}:
            return leaves[skel["__leaf__"]]
        if set(skel) == {"__tuple__"}:
            return tuple(_tree_build(v, leaves) for v in skel["__tuple__"])
        return {k: _tree_build(v, leaves) for k, v in skel.items()}
    if isinstance(skel, list):
        return [_tree_build(v, leaves) for v in skel]
    raise PayloadMismatchError(f"malformed state skeleton node {skel!r}")


# ---------------------------------------------------------------------------
# SharedKV transfers: the sender and receiver halves
# ---------------------------------------------------------------------------
def _put_wire(arrays: Dict[str, np.ndarray], name: str, x,
              wire_dtype) -> int:
    """Encode ``x`` into the frame's array dict.  Uniform wires keep the
    legacy ``name`` / ``name@scale`` layout; a ``WirePlan`` emits the
    group-ordered tuple as ``name@p0``, ``name@p1``, ... so the receiver
    can re-thread the exact arity the plan spec implies."""
    wire, n = encode_wire(x, wire_dtype)
    if as_wire_plan(wire_dtype) is not None:
        for i, arr in enumerate(wire):
            arrays[f"{name}@p{i}"] = arr
        return n
    arrays[name] = wire[0]
    if len(wire) > 1:
        arrays[name + "@scale"] = wire[1]
    return n


def _take_wire(arrays: Dict[str, np.ndarray], name: str, wire_dtype,
               dtype) -> jnp.ndarray:
    try:
        plan = as_wire_plan(wire_dtype)
        if plan is not None:
            from repro.comm.transport import wire_array_count
            wire = tuple(arrays[f"{name}@p{i}"]
                         for i in range(wire_array_count(plan)))
        else:
            wire = (arrays[name],)
            if wire_has_scales(wire_dtype):
                wire = (arrays[name], arrays[name + "@scale"])
    except KeyError as e:
        raise PayloadMismatchError(f"frame lacks array {e.args[0]!r}") \
            from None
    return decode_wire(wire, wire_dtype, dtype)


def encode_kv_transfer(kvcfg: KVCommConfig, kv, select=None, states=None,
                       state_select=None,
                       assignment: Optional[LayerAssignment] = None,
                       wire_dtype: str = "float16",
                       packed: bool = True) -> Tuple[bytes, int, int, int]:
    """The sender half: gather the selected (or assignment-mapped) layers,
    wire-cast them, and frame the result.

    Returns ``(frame bytes, payload wire bytes, layer count, prefix_len)``
    — payload bytes are exactly what ``SerializedTransport`` would count
    for the same transfer (the shared codec guarantees it)."""
    wire_dtype = resolve_wire_dtype(wire_dtype)
    arrays: Dict[str, np.ndarray] = {}
    n_bytes = 0
    prefix_len = 0
    kv_meta = None
    if assignment is not None:
        layer_count = assignment.num_pairs
        sel_mask = [bool(b) for b in assignment.dst_mask()]
        layers = list(assignment.dst)
        src_layers = list(assignment.src)
        src_idx = np.asarray(assignment.src, np.int32)
    else:
        layer_count = selected_count(select)
        sel_mask = (None if select is None
                    else [bool(b) for b in np.asarray(select)])
        layers = (None if select is None
                  else list(selected_layer_ids(select)))
        src_layers = None
        src_idx = (None if layers is None
                   else np.asarray(layers, np.int32))
    if kv is not None:
        if src_idx is None:
            raise ValueError("a remote KV transfer needs a selection mask "
                             "or a LayerAssignment")
        prefix_len = int(kv["k"].shape[2])
        compute_dtype = np.dtype(kv["k"].dtype).name
        for part in ("k", "v"):
            n_bytes += _put_wire(arrays, part, kv[part][src_idx], wire_dtype)
        kv_meta = {"prefix_len": prefix_len, "pos_mode": kvcfg.pos_mode,
                   "packed": packed, "layers": layers,
                   "src_layers": src_layers, "select": sel_mask,
                   "compute_dtype": compute_dtype}
    state_meta = None
    if states is not None and state_select is not None:
        skel, leaves = _tree_parts(states)
        sel = np.nonzero(np.asarray(state_select))[0]
        # a per-selected-slot plan cannot index full-depth state stacks:
        # state leaves ship at the plan's finest tier (uniform wires pass
        # through unchanged)
        state_wd = state_wire_dtype(wire_dtype)
        shapes, dtypes = [], []
        for i, leaf in enumerate(leaves):
            leaf = jnp.asarray(leaf)
            shapes.append(list(leaf.shape))
            dtypes.append(np.dtype(leaf.dtype).name)
            n_bytes += _put_wire(arrays, f"s{i}", leaf[sel], state_wd)
        state_meta = {"skeleton": skel, "shapes": shapes, "dtypes": dtypes,
                      "select": [bool(b) for b in np.asarray(state_select)]}
    meta = {"wire_dtype": wire_spec(wire_dtype), "kv": kv_meta,
            "states": state_meta, "pos_mode": kvcfg.pos_mode,
            "sel_mask": sel_mask if kv is None else None}
    return (encode_frame("shared_kv", meta, arrays), n_bytes, layer_count,
            prefix_len)


def _decode_states(state_meta, arrays: Dict[str, np.ndarray], wire_dtype):
    """Rebuild the dense state pytree (+ its select mask) from a frame's
    ``s{i}`` arrays; the one states decoder the monolithic and streaming
    receive paths share.  Returns ``(states, state_select)`` — both None
    when the transfer carried no states."""
    if state_meta is None:
        return None, None
    try:
        sel = np.asarray(state_meta["select"], bool)
        shapes = state_meta["shapes"]
        dtypes = state_meta["dtypes"]
        skel = state_meta["skeleton"]
    except (KeyError, TypeError) as e:
        raise PayloadMismatchError(f"state meta lacks {e}") from None
    idx = np.nonzero(sel)[0]
    leaves = []
    state_wd = state_wire_dtype(wire_dtype)
    for i, (shape, dname) in enumerate(zip(shapes, dtypes)):
        part = _take_wire(arrays, f"s{i}", state_wd, _np_dtype(dname))
        want = (len(idx),) + tuple(shape[1:])
        if tuple(part.shape) != want:
            raise PayloadMismatchError(
                f"state leaf {i} shape {tuple(part.shape)} != "
                f"expected {want}")
        dense = jnp.zeros(tuple(shape), _np_dtype(dname))
        leaves.append(dense.at[idx].set(part) if len(idx) else dense)
    return _tree_build(skel, leaves), jnp.asarray(sel)


def decode_kv_transfer(meta: Dict[str, Any], arrays: Dict[str, np.ndarray]
                       ) -> Tuple[SharedKV, int]:
    """The receiver half: validate a decoded ``shared_kv`` frame and
    rebuild the packed RECEIVER-keyed ``SharedKV`` view (densified when the
    sender asked for the legacy dense form).  Returns (view, wire bytes)."""
    try:
        wire_dtype = meta["wire_dtype"]
        kv_meta, state_meta = meta["kv"], meta["states"]
    except (KeyError, TypeError) as e:
        raise PayloadMismatchError(f"shared_kv frame meta lacks {e}") \
            from None
    try:
        wire_dtype = resolve_wire_dtype(wire_dtype)
    except ValueError:
        raise PayloadMismatchError(f"unknown wire dtype {wire_dtype!r}") \
            from None
    n_bytes = int(sum(a.nbytes for a in arrays.values()))
    payload = None
    if kv_meta is not None:
        dtype = _np_dtype(kv_meta.get("compute_dtype", "float32"))
        payload = {part: _take_wire(arrays, part, wire_dtype, dtype)
                   for part in ("k", "v")}
        if payload["k"].shape != payload["v"].shape:
            raise PayloadMismatchError(
                f"k/v shapes disagree: {payload['k'].shape} "
                f"vs {payload['v'].shape}")
        if payload["k"].ndim != 5:
            raise PayloadMismatchError(
                f"KV payload must be (M, B, Sc, Hkv, Dh); "
                f"got rank {payload['k'].ndim}")
        layers = kv_meta.get("layers")
        if layers is not None and len(layers) != payload["k"].shape[0]:
            raise PayloadMismatchError(
                f"layer map names {len(layers)} layers but the payload "
                f"stacks {payload['k'].shape[0]}")
        if int(payload["k"].shape[2]) != int(kv_meta["prefix_len"]):
            raise PayloadMismatchError(
                f"header prefix_len {kv_meta['prefix_len']} != payload "
                f"Sc {payload['k'].shape[2]}")
    states, state_select = _decode_states(state_meta, arrays, wire_dtype)
    if kv_meta is None:
        sel_mask = meta.get("sel_mask")
        shared = SharedKV(
            kv=None,
            select=None if sel_mask is None else jnp.asarray(sel_mask, bool),
            states=states, state_select=state_select,
            prefix_len=0, pos_mode=meta.get("pos_mode", "shift"))
        return shared, n_bytes
    try:
        shared = SharedKV.from_wire(kv_meta, payload, states=states,
                                    state_select=state_select)
    except (KeyError, TypeError, ValueError) as e:
        raise PayloadMismatchError(f"cannot rebuild SharedKV: {e}") \
            from None
    return shared, n_bytes


# ---------------------------------------------------------------------------
# streaming chunked transfers: kv_stream_begin / kv_stream_chunk /
# kv_stream_end
# ---------------------------------------------------------------------------
# The monolithic shared_kv frame serializes the WHOLE selected stack before
# the first byte moves — on long contexts that makes serialize ~90% of the
# remote wall clock.  The streaming framing splits the same payload into
# per-slot, sequence-sliced chunks of roughly DEFAULT_CHUNK_BYTES so the
# sender's encode of chunk i+1 overlaps the channel write and the
# receiver's decode of chunk i.  The chunk codec is the SAME encode_wire
# per layer slot (per-layer scales are slice-invariant), so the streamed
# bytes and the rebuilt view are bit-identical to the monolithic frame.
# The receiver installs NOTHING until the end frame arrives and every slot
# is fully covered — a retried/replayed stream (fresh sid) is idempotent
# per-chunk by construction.
DEFAULT_CHUNK_BYTES = 1 << 20


class KVStreamSender:
    """Sender half of a chunked KV transfer: same selection/meta plumbing
    as ``encode_kv_transfer``, but ``frames()`` lazily yields
    ``(frame_bytes, payload_bytes)`` one bounded chunk at a time — each
    ``next()`` does that chunk's wire-cast, so a driver interleaves encode
    with channel writes."""

    def __init__(self, kvcfg: KVCommConfig, kv, select=None, states=None,
                 state_select=None,
                 assignment: Optional[LayerAssignment] = None,
                 wire_dtype="float16", packed: bool = True,
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                 sid: int = 0) -> None:
        from repro.comm.transport import _WIRE_BITS
        self.wire_dtype = resolve_wire_dtype(wire_dtype)
        self.chunk_bytes = max(int(chunk_bytes), 1)
        self.sid = int(sid)
        self.kvcfg = kvcfg
        self.states, self.state_select = states, state_select
        if assignment is not None:
            self.layer_count = assignment.num_pairs
            sel_mask = [bool(b) for b in assignment.dst_mask()]
            layers = list(assignment.dst)
            src_layers = list(assignment.src)
            src_idx = np.asarray(assignment.src, np.int32)
        else:
            self.layer_count = selected_count(select)
            sel_mask = (None if select is None
                        else [bool(b) for b in np.asarray(select)])
            layers = (None if select is None
                      else list(selected_layer_ids(select)))
            src_layers = None
            src_idx = (None if layers is None
                       else np.asarray(layers, np.int32))
        self._sel_mask = sel_mask
        self.prefix_len = 0
        self._payload = None
        self._host = None
        self._kv_meta = None
        self._kv_shape = None
        self._slot_dtypes: list = []
        if kv is not None:
            if src_idx is None:
                raise ValueError("a remote KV transfer needs a selection "
                                 "mask or a LayerAssignment")
            self.prefix_len = int(kv["k"].shape[2])
            compute_dtype = np.dtype(kv["k"].dtype).name
            # float32 payloads gather AND encode slot-by-slot in pure
            # numpy: np.asarray of a host-backend jax array is
            # (near-)zero-copy, so one numpy take replaces the device
            # gather plus a full-payload host materialization, and no
            # jnp dispatch runs per slot (per-slot device round-trips
            # cost as much as the whole monolithic encode).  Other
            # compute dtypes keep the jnp codec, whose scale math
            # np_encode_wire only mirrors for float32.
            if compute_dtype == "float32":
                idx = np.asarray(src_idx)
                self._host = {part: np.asarray(kv[part])[idx]
                              for part in ("k", "v")}
                stack = self._host["k"]
            else:
                self._payload = {part: jnp.asarray(kv[part])[src_idx]
                                 for part in ("k", "v")}
                stack = self._payload["k"]
            self._kv_shape = [int(d) for d in stack.shape]
            m_slots = self._kv_shape[0]
            plan = as_wire_plan(self.wire_dtype)
            if plan is not None:
                if len(plan) != m_slots:
                    raise ValueError(f"wire plan covers {len(plan)} slots "
                                     f"but the transfer has {m_slots}")
                self._slot_dtypes = list(plan.dtypes)
            else:
                self._slot_dtypes = [self.wire_dtype] * m_slots
            self._kv_meta = {"prefix_len": self.prefix_len,
                             "pos_mode": kvcfg.pos_mode, "packed": packed,
                             "layers": layers, "src_layers": src_layers,
                             "select": sel_mask,
                             "compute_dtype": compute_dtype}
        # chunk plan: slot-major, each slot sequence-sliced so one chunk's
        # k+v wire stays within ~chunk_bytes
        self._chunks: list = []
        if self._kv_shape is not None:
            _, b, sc, h, d = self._kv_shape
            for m, dt in enumerate(self._slot_dtypes):
                bits = _WIRE_BITS[dt]
                bytes_per_pos = max((2 * b * h * d * bits) // 8, 1)
                step = max(self.chunk_bytes // bytes_per_pos, 1)
                start = 0
                while start < sc:
                    length = min(step, sc - start)
                    self._chunks.append((m, start, length))
                    start += length
        self.n_frames = 2 + len(self._chunks)

    def _encode_slots(self):
        """Wire-encode the payload one dtype GROUP at a time and hand back
        per-slot views: per-layer scales live on the leading axis, so a
        group encode is bit-equal to slot-by-slot encodes, and one
        vectorized cast beats M small ones (numpy has no SIMD fp16 cast
        here — float wires go through the jnp codec, scaled wires through
        the numpy quantizer, both one call per group)."""
        from repro.comm.transport import _SCALED_WIRES, _WIRE_DTYPES
        slot_wire: Dict[str, Dict[int, tuple]] = {"k": {}, "v": {}}
        if self._kv_shape is None:
            return slot_wire
        groups: Dict[str, list] = {}
        for i, dt in enumerate(self._slot_dtypes):
            groups.setdefault(dt, []).append(i)
        for dt, slots in groups.items():
            whole = len(slots) == len(self._slot_dtypes)
            for part in ("k", "v"):
                if self._host is not None:
                    sub = (self._host[part] if whole
                           else self._host[part][np.asarray(slots)])
                    if dt in _SCALED_WIRES:
                        wire = np_encode_wire(sub, dt)[0]
                    else:
                        wire = (np.asarray(jnp.asarray(sub).astype(
                            _WIRE_DTYPES[dt])),)
                else:
                    stack = self._payload[part]
                    sub = stack if whole else stack[np.asarray(slots)]
                    wire = encode_wire(sub, dt)[0]
                for j, m in enumerate(slots):
                    slot_wire[part][m] = tuple(a[j:j + 1] for a in wire)
        return slot_wire

    def frames(self):
        meta = {"sid": self.sid, "wire_dtype": wire_spec(self.wire_dtype),
                "kv": self._kv_meta, "kv_shape": self._kv_shape,
                "pos_mode": self.kvcfg.pos_mode,
                "sel_mask": self._sel_mask if self._kv_meta is None
                else None,
                "chunks": len(self._chunks)}
        yield encode_frame("kv_stream_begin", meta, {}), 0
        slot_wire = self._encode_slots()
        seq = 0
        for (m, start, length) in self._chunks:
            arrays: Dict[str, np.ndarray] = {}
            nb = 0
            for part in ("k", "v"):
                wire = slot_wire[part][m]
                piece = wire[0][:, :, start:start + length]
                arrays[part] = piece
                nb += piece.nbytes
                if len(wire) > 1:
                    # the scale rides EVERY chunk (self-decodable) but is
                    # counted once per slot, so streamed n_bytes matches
                    # the monolithic/analytic accounting
                    arrays[part + "@scale"] = wire[1]
                    if start == 0:
                        nb += wire[1].nbytes
            meta = {"sid": self.sid, "seq": seq, "slot": m,
                    "start": start, "length": length}
            yield encode_frame("kv_stream_chunk", meta, arrays), nb
            seq += 1
        arrays = {}
        nb = 0
        state_meta = None
        if self.states is not None and self.state_select is not None:
            skel, leaves = _tree_parts(self.states)
            sel = np.nonzero(np.asarray(self.state_select))[0]
            state_wd = state_wire_dtype(self.wire_dtype)
            shapes, dtypes = [], []
            for i, leaf in enumerate(leaves):
                leaf = jnp.asarray(leaf)
                shapes.append(list(leaf.shape))
                dtypes.append(np.dtype(leaf.dtype).name)
                nb += _put_wire(arrays, f"s{i}", leaf[sel], state_wd)
            state_meta = {
                "skeleton": skel, "shapes": shapes, "dtypes": dtypes,
                "select": [bool(b)
                           for b in np.asarray(self.state_select)]}
        meta = {"sid": self.sid, "seq": seq,
                "chunks": len(self._chunks), "states": state_meta}
        yield encode_frame("kv_stream_end", meta, arrays), nb


class KVStreamAssembler:
    """Receiver half: feed it stream frames in order; returns
    ``(SharedKV, payload_bytes)`` on the end frame, ``None`` before.  A
    fresh ``kv_stream_begin`` replaces any in-progress stream (replayed
    transfers restart under a new sid — nothing was installed, so the
    retry is idempotent); every inconsistency raises a typed
    ``PayloadMismatchError``."""

    def __init__(self) -> None:
        self._s: Optional[Dict[str, Any]] = None

    @property
    def active(self) -> bool:
        return self._s is not None

    def abort(self) -> None:
        self._s = None

    def feed(self, kind: str, meta: Dict[str, Any],
             arrays: Dict[str, np.ndarray]
             ) -> Optional[Tuple[SharedKV, int]]:
        # any protocol violation aborts the in-progress stream: a broken
        # frame sequence cannot be resumed (frames arrive in order on a
        # serial channel), and the sender's retry restarts with a fresh
        # begin regardless — nothing partial may linger as "active"
        try:
            if kind == "kv_stream_begin":
                return self._begin(meta)
            st = self._s
            if st is None:
                raise PayloadMismatchError(
                    f"{kind!r} frame without an active stream begin")
            if meta.get("sid") != st["sid"]:
                raise PayloadMismatchError(
                    f"stream sid mismatch: frame {meta.get('sid')!r} vs "
                    f"active {st['sid']!r}")
            if kind == "kv_stream_chunk":
                return self._chunk(meta, arrays)
            if kind == "kv_stream_end":
                return self._end(meta, arrays)
            raise PayloadMismatchError(
                f"unexpected frame kind {kind!r} mid-stream")
        except RemoteProtocolError:
            self._s = None
            raise

    def _begin(self, meta: Dict[str, Any]) -> None:
        try:
            sid = int(meta["sid"])
            wire_dtype = resolve_wire_dtype(meta["wire_dtype"])
            kv_meta = meta["kv"]
            chunks = int(meta["chunks"])
        except (KeyError, TypeError, ValueError) as e:
            raise PayloadMismatchError(
                f"kv_stream_begin meta invalid: {e}") from None
        bufs = shape = None
        slot_dtypes: list = []
        if kv_meta is not None:
            shape = meta.get("kv_shape")
            if (not isinstance(shape, (list, tuple)) or len(shape) != 5
                    or any(int(d) < 0 for d in shape)):
                raise PayloadMismatchError(
                    f"kv_stream_begin kv_shape invalid: {shape!r}")
            shape = tuple(int(d) for d in shape)
            if shape[2] != int(kv_meta.get("prefix_len", -1)):
                raise PayloadMismatchError(
                    f"kv_shape Sc {shape[2]} != header prefix_len "
                    f"{kv_meta.get('prefix_len')!r}")
            layers = kv_meta.get("layers")
            if layers is not None and len(layers) != shape[0]:
                raise PayloadMismatchError(
                    f"layer map names {len(layers)} layers but the "
                    f"stream ships {shape[0]}")
            plan = as_wire_plan(wire_dtype)
            if plan is not None and len(plan) != shape[0]:
                raise PayloadMismatchError(
                    f"wire plan covers {len(plan)} slots but the stream "
                    f"ships {shape[0]}")
            dtype = _np_dtype(kv_meta.get("compute_dtype", "float32"))
            bufs = {part: np.zeros(shape, dtype) for part in ("k", "v")}
            slot_dtypes = (list(plan.dtypes) if plan is not None
                           else [wire_dtype] * shape[0])
        elif chunks:
            raise PayloadMismatchError(
                f"stream claims {chunks} chunks but carries no KV")
        self._s = {"sid": sid, "wire_dtype": wire_dtype,
                   "kv_meta": kv_meta, "begin": meta, "chunks": chunks,
                   "seq": 0, "bufs": bufs, "shape": shape,
                   "slot_dtypes": slot_dtypes,
                   "next": [0] * (shape[0] if shape else 0),
                   "n_bytes": 0}
        return None

    def _chunk(self, meta: Dict[str, Any],
               arrays: Dict[str, np.ndarray]) -> None:
        st = self._s
        try:
            seq = int(meta["seq"])
            slot = int(meta["slot"])
            start = int(meta["start"])
            length = int(meta["length"])
        except (KeyError, TypeError, ValueError) as e:
            raise PayloadMismatchError(
                f"kv_stream_chunk meta invalid: {e}") from None
        if st["bufs"] is None:
            raise PayloadMismatchError("chunk for a KV-less stream")
        if seq != st["seq"]:
            raise PayloadMismatchError(
                f"stream chunk out of order: seq {seq}, "
                f"expected {st['seq']}")
        m_slots, b, sc, h, d = st["shape"]
        if not 0 <= slot < m_slots:
            raise PayloadMismatchError(
                f"chunk slot {slot} outside [0, {m_slots})")
        if start != st["next"][slot]:
            raise PayloadMismatchError(
                f"non-contiguous chunk for slot {slot}: start {start}, "
                f"expected {st['next'][slot]}")
        if length <= 0 or start + length > sc:
            raise PayloadMismatchError(
                f"chunk range [{start}, {start + length}) outside the "
                f"{sc}-position prefix")
        dt = st["slot_dtypes"][slot]
        dtype = st["bufs"]["k"].dtype
        for part in ("k", "v"):
            try:
                wire = (arrays[part],)
                if wire_has_scales(dt):
                    wire = (arrays[part], arrays[part + "@scale"])
            except KeyError as e:
                raise PayloadMismatchError(
                    f"stream chunk lacks array {e.args[0]!r}") from None
            # pure-numpy decode: a jnp dispatch per bounded chunk would
            # stall the pipeline (the receiver, not the channel, becomes
            # the bottleneck and backpressure blocks the sender)
            dec = np_decode_wire(wire, dt, dtype)
            if tuple(dec.shape) != (1, b, length, h, d):
                raise PayloadMismatchError(
                    f"chunk decodes to {tuple(dec.shape)}, expected "
                    f"{(1, b, length, h, d)}")
            st["bufs"][part][slot, :, start:start + length] = dec[0]
            st["n_bytes"] += arrays[part].nbytes
            if wire_has_scales(dt) and start == 0:
                st["n_bytes"] += arrays[part + "@scale"].nbytes
        st["seq"] += 1
        st["next"][slot] = start + length
        return None

    def _end(self, meta: Dict[str, Any], arrays: Dict[str, np.ndarray]
             ) -> Tuple[SharedKV, int]:
        st = self._s
        if st["seq"] != st["chunks"] \
                or int(meta.get("chunks", -1)) != st["chunks"]:
            raise PayloadMismatchError(
                f"stream ended after {st['seq']}/{st['chunks']} chunks")
        if st["bufs"] is not None:
            _, _, sc, _, _ = st["shape"]
            for m, covered in enumerate(st["next"]):
                if covered != sc:
                    raise PayloadMismatchError(
                        f"stream slot {m} covered {covered}/{sc} "
                        "positions at end")
        states, state_select = _decode_states(meta.get("states"), arrays,
                                              st["wire_dtype"])
        n_bytes = st["n_bytes"] + int(sum(a.nbytes
                                          for a in arrays.values()))
        if st["kv_meta"] is None:
            begin = st["begin"]
            sel_mask = begin.get("sel_mask")
            shared = SharedKV(
                kv=None,
                select=(None if sel_mask is None
                        else jnp.asarray(sel_mask, bool)),
                states=states, state_select=state_select,
                prefix_len=0, pos_mode=begin.get("pos_mode", "shift"))
        else:
            payload = {part: jnp.asarray(st["bufs"][part])
                       for part in ("k", "v")}
            try:
                shared = SharedKV.from_wire(st["kv_meta"], payload,
                                            states=states,
                                            state_select=state_select)
            except (KeyError, TypeError, ValueError) as e:
                raise PayloadMismatchError(
                    f"cannot rebuild SharedKV: {e}") from None
        self._s = None
        return shared, n_bytes


def send_shared(channel: RemoteChannel, kvcfg: KVCommConfig, kv, select=None,
                *, states=None, state_select=None,
                assignment: Optional[LayerAssignment] = None,
                wire_dtype="float16", packed: bool = True,
                chunk_bytes: Optional[int] = None, sid: int = 0) -> int:
    """Sender-process entry: frame one KV transfer onto the channel.
    ``chunk_bytes=None`` writes the single monolithic ``shared_kv`` frame;
    an int streams begin/chunk/end frames bounded by roughly that size.
    Returns the payload wire bytes (what the analytics predict) either
    way."""
    if chunk_bytes is None:
        frame, n_bytes, _, _ = encode_kv_transfer(
            kvcfg, kv, select, states, state_select, assignment,
            wire_dtype, packed)
        channel.write(frame)
        return n_bytes
    sender = KVStreamSender(kvcfg, kv, select, states, state_select,
                            assignment, wire_dtype, packed,
                            chunk_bytes=chunk_bytes, sid=sid)
    n_bytes = 0
    for frame, nb in sender.frames():
        channel.write(frame)
        n_bytes += nb
    return n_bytes


def recv_shared(channel: RemoteChannel) -> Tuple[SharedKV, int]:
    """Receiver-process entry: read one KV transfer — a monolithic
    ``shared_kv`` frame or a complete ``kv_stream_*`` sequence — and
    rebuild the receiver-side view.  Returns (SharedKV, payload wire
    bytes)."""
    kind, meta, arrays = read_frame(channel)
    if kind == "shared_kv":
        return decode_kv_transfer(meta, arrays)
    if kind == "kv_stream_begin":
        asm = KVStreamAssembler()
        out = asm.feed(kind, meta, arrays)
        while out is None:
            out = asm.feed(*read_frame(channel))
        return out
    raise PayloadMismatchError(
        f"expected a shared_kv or kv_stream_begin frame, got {kind!r}")


# ---------------------------------------------------------------------------
# the Transport
# ---------------------------------------------------------------------------
class RemoteTransport(Transport):
    """Ships the gathered selected-layer payload through the framed codec
    and a byte channel, and hands back the DECODED receiver-side view.

    With the default ``LoopbackChannel`` the whole round trip (gather ->
    wire cast -> frame -> channel -> parse -> device put) runs in-process —
    byte-identical frames to the cross-process path, so the conformance
    suite and the serving scheduler exercise the real codec.  A duplex
    channel whose ``read`` returns the peer's response frames (e.g. an echo
    service over ``SocketChannel``) works the same way; the pure two-process
    split uses the ``send_shared`` / ``recv_shared`` halves directly
    (``repro.launch.remote_serve``).

    The ``TransferRecord`` carries the remote breakdown: ``serialize_s``
    (gather + wire cast + framing), ``channel_s`` (channel write + read
    back), ``deserialize_s`` (parse + rebuild), plus ``frame_bytes`` (full
    frame incl. header/CRC) next to the analytics-matching ``n_bytes``.

    Fault tolerance (``repro.comm.resilience``): a ``policy``
    (``RetryPolicy``) re-runs a failed exchange over a healed channel —
    ``channel_factory`` reconnects (fresh channel per retry attempt), a
    channel exposing ``reset()`` (``FaultyChannel``) is reset in place.
    Retries are idempotent by construction: the unpaged exchange re-frames
    the same deterministic payload, and a paged retry re-runs
    ``page_query`` against the (possibly partially filled) pool, so the
    resend ships ONLY the pages the receiver never pooled.  An optional
    ``breaker`` (``CircuitBreaker``) short-circuits sends while its peer
    is quarantined.  The successful record's ``attempts`` counts what the
    transfer burned.
    """

    def __init__(self, wire_dtype="float16",
                 channel: Optional[RemoteChannel] = None,
                 packed: bool = True, sync: bool = True,
                 store=None, policy=None, channel_factory=None,
                 breaker=None,
                 chunk_bytes: Optional[int] = DEFAULT_CHUNK_BYTES) -> None:
        super().__init__(packed=packed, sync=sync, store=store)
        self.wire_dtype = resolve_wire_dtype(wire_dtype)
        # unpaged transfers stream in ~chunk_bytes pieces (the default);
        # None falls back to the single monolithic shared_kv frame
        self.chunk_bytes = chunk_bytes
        self.policy = policy                    # resilience.RetryPolicy
        self.channel_factory = channel_factory  # () -> RemoteChannel
        self.breaker = breaker                  # resilience.CircuitBreaker
        if channel is None:
            channel = (channel_factory() if channel_factory is not None
                       else LoopbackChannel())
        self.channel = channel
        self._paged_rx = None          # lazy PagedReceiver over self.store
        self._xid = 0                  # paged exchange counter
        self._sid = 0                  # stream id counter (fresh per try)

    # -- retry plumbing ----------------------------------------------------
    def _reset_channel(self) -> None:
        """Heal the channel between retry attempts: drop any pending paged
        exchange state (a died handshake's expectations), then reconnect
        via the factory or reset the channel in place."""
        if self._paged_rx is not None:
            self._paged_rx.abort()
        if self.channel_factory is not None:
            try:
                self.channel.close()
            except (RemoteProtocolError, OSError):
                pass
            self.channel = self.channel_factory()
        elif hasattr(self.channel, "reset"):
            self.channel.reset()

    def _attempt(self, fn, describe: str):
        """Run one exchange under the breaker + retry policy.  ``fn`` must
        be self-contained (appends its own TransferRecord on success); the
        record's ``attempts`` is stamped here."""
        from repro.comm.resilience import CircuitOpenError
        if self.breaker is not None and not self.breaker.allow():
            raise CircuitOpenError(
                f"{describe}: peer circuit is open (quarantined after "
                f"{self.breaker.failures} consecutive failures)")
        used = [1]

        def wrapped(attempt: int):
            used[0] = attempt + 1
            if attempt:
                self._reset_channel()
            return fn()

        try:
            out = wrapped(0) if self.policy is None \
                else self.policy.run(wrapped, describe=describe)
        except (RemoteProtocolError, OSError):
            if self.breaker is not None:
                self.breaker.record_failure()
            raise
        if self.breaker is not None:
            self.breaker.record_success()
        self.log[-1].attempts = used[0]
        return out

    def _ship(self, kvcfg: KVCommConfig, kv, select, states, state_select,
              assignment: Optional[LayerAssignment]) -> SharedKV:
        return self._attempt(
            lambda: self._ship_once(kvcfg, kv, select, states,
                                    state_select, assignment),
            describe="remote shared_kv exchange")

    def _ship_once(self, kvcfg: KVCommConfig, kv, select, states,
                   state_select,
                   assignment: Optional[LayerAssignment]) -> SharedKV:
        if self.chunk_bytes is not None:
            return self._ship_streamed(kvcfg, kv, select, states,
                                       state_select, assignment)
        clock = spans.WireClock()
        with clock(spans.WIRE_ENCODE):
            frame, n_bytes, layer_count, prefix_len = encode_kv_transfer(
                kvcfg, kv, select, states, state_select, assignment,
                self.wire_dtype, self.packed)
        with clock(spans.WIRE_CHANNEL):
            self.channel.write(frame)
            kind, meta, arrays = read_frame(self.channel)
        if kind != "shared_kv":
            raise PayloadMismatchError(
                f"expected a shared_kv frame, got {kind!r}")
        with clock(spans.WIRE_DECODE):
            shared, n_decoded = decode_kv_transfer(meta, arrays)
        self.log.append(TransferRecord(
            kind="kv", n_bytes=n_decoded, layers=layer_count,
            context_len=prefix_len,
            wire_dtype=wire_spec(self.wire_dtype),
            frame_bytes=len(frame), **clock.fields()))
        return shared

    def _ship_streamed(self, kvcfg: KVCommConfig, kv, select, states,
                       state_select,
                       assignment: Optional[LayerAssignment]) -> SharedKV:
        """Chunked exchange over the loopback/echo channel: each stream
        frame is encoded (serialize_s), written + echoed back (channel_s)
        and fed to the assembler (deserialize_s) before the NEXT chunk is
        encoded — the chunked cost structure a cross-process driver
        overlaps.  A retry restarts under a fresh sid; the assembler
        installs nothing until the end frame, so replay is idempotent."""
        sid, self._sid = self._sid, self._sid + 1
        sender = KVStreamSender(kvcfg, kv, select, states, state_select,
                                assignment, self.wire_dtype, self.packed,
                                chunk_bytes=self.chunk_bytes, sid=sid)
        asm = KVStreamAssembler()
        frames = sender.frames()
        clock = spans.WireClock()
        frame_bytes = 0
        out = None
        while out is None:
            with clock(spans.WIRE_ENCODE):
                try:
                    frame, _ = next(frames)
                except StopIteration:  # pragma: no cover - assembler ends 1st
                    raise PayloadMismatchError(
                        "KV stream exhausted before the end frame resolved")
            frame_bytes += len(frame)
            with clock(spans.WIRE_CHANNEL):
                self.channel.write(frame)
                kind, meta, arrays = read_frame(self.channel)
            with clock(spans.WIRE_DECODE):
                out = asm.feed(kind, meta, arrays)
        shared, n_bytes = out
        self.log.append(TransferRecord(
            kind="kv", n_bytes=n_bytes, layers=sender.layer_count,
            context_len=sender.prefix_len,
            wire_dtype=wire_spec(self.wire_dtype),
            frame_bytes=frame_bytes, **clock.fields()))
        return shared

    def _send(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv, select,
              states=None, state_select=None) -> SharedKV:
        return self._ship(kvcfg, kv, select, states, state_select, None)

    def _send_mapped(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv,
                     assignment: LayerAssignment, states=None,
                     state_select=None) -> SharedKV:
        return self._ship(kvcfg, kv, None, states, state_select, assignment)

    # -- the paged (content-addressed) wire --------------------------------
    def _send_paged(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv,
                    select, states=None, state_select=None,
                    assignment: Optional[LayerAssignment] = None
                    ) -> SharedKV:
        """The dedup-aware three-frame exchange (``repro.store.wire``):
        ``page_query`` carries the block table (+ int8 scales),
        ``page_need`` answers with the pool's missing IDs, ``page_data``
        ships only those pages (+ states).  As with ``_ship``, one object
        plays both roles over its channel — frames byte-identical to the
        two-process split ``launch.remote_serve`` drives.

        A retried exchange re-asks ``page_query`` with a FRESH xid: pages
        that survived a truncated ``page_data`` (hash-verified before
        pooling) answer as hits, so the resend carries only what the pool
        genuinely never got — retry bytes are bounded by novel-page
        bytes."""
        return self._attempt(
            lambda: self._send_paged_once(cfg, kvcfg, kv, select, states,
                                          state_select, assignment),
            describe="paged page_query/need/data exchange")

    def _send_paged_once(self, cfg: ModelConfig, kvcfg: KVCommConfig, kv,
                         select, states=None, state_select=None,
                         assignment: Optional[LayerAssignment] = None
                         ) -> SharedKV:
        # deferred so repro.comm never hard-depends on repro.store at
        # import time (the store package imports this module's codec)
        from repro.store.paging import split_payload
        from repro.store.wire import (PagedReceiver, decode_page_need,
                                      encode_page_data, encode_page_query)
        if self._paged_rx is None or self._paged_rx.store is not self.store:
            self._paged_rx = PagedReceiver(self.store)
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
        xid, self._xid = self._xid, self._xid + 1
        clock = spans.WireClock()
        with clock(spans.WIRE_ENCODE):
            table, pages = split_payload(
                payload, layers=layers, select=sel_mask,
                page_len=self.store.page_len, wire_dtype=self.wire_dtype,
                pos_mode=kvcfg.pos_mode, src_layers=src_layers)
            by_id = {p.page_id: p for p in pages}
            qframe = encode_page_query(xid, table)
        with clock(spans.WIRE_CHANNEL):
            self.channel.write(qframe)
            kind, meta, arrays = read_frame(self.channel)
        if kind != "page_query":
            raise PayloadMismatchError(
                f"expected a page_query frame, got {kind!r}")
        # the receiver answers the query with the pages it lacks
        with clock(spans.WIRE_DECODE):
            need_frame = self._paged_rx.handle_query(meta, arrays)
        with clock(spans.WIRE_CHANNEL):
            self.channel.write(need_frame)
            kind, meta, _ = read_frame(self.channel)
        if kind != "page_need":
            raise PayloadMismatchError(
                f"expected a page_need frame, got {kind!r}")
        with clock(spans.WIRE_ENCODE):
            _, need = decode_page_need(meta)
            dframe, _ = encode_page_data(
                xid, [by_id[pid] for pid in need],
                wire_dtype=self.wire_dtype, states=states,
                state_select=state_select)
        with clock(spans.WIRE_CHANNEL):
            self.channel.write(dframe)
            kind, meta, arrays = read_frame(self.channel)
        if kind != "page_data":
            raise PayloadMismatchError(
                f"expected a page_data frame, got {kind!r}")
        with clock(spans.WIRE_DECODE):
            shared, table_rx, novel_bytes, state_bytes = \
                self._paged_rx.handle_data(meta, arrays)
            # handle_data left table_rx pinned; anything failing between
            # here and a successful swap must release it or the refcounts
            # leak
            try:
                if not self.packed:
                    shared = shared.to_dense()
                self._swap_table(table_rx)
            except BaseException:
                self.store.release(table_rx)
                raise
        self.log.append(TransferRecord(
            kind="kv",
            n_bytes=novel_bytes + table_rx.scale_nbytes + state_bytes,
            layers=layer_count, context_len=table.prefix_len,
            wire_dtype=wire_spec(self.wire_dtype),
            frame_bytes=len(qframe) + len(need_frame) + len(dframe),
            **clock.fields(),
            pages_total=table.num_pages, pages_sent=len(need),
            pages_hit=table.num_pages - len(need)))
        return shared
