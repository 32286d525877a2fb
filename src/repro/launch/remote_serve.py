"""Two-process KV serving: a sender-side client shipping selected KV to a
receiver-side server over the framed remote codec.

This is the disaggregated deployment the ROADMAP's "remote transport" item
asks for (LMCache-style KV residency: the context-holding sender and the
query-answering receiver live in different processes, possibly different
hosts), built on ``repro.comm.remote``:

  kv_server — owns the RECEIVER model.  Accepts one client connection and
              serves a tiny frame protocol: ``shared_kv`` frames install the
              current sender prefix (decoded through ``recv_shared`` into
              the packed receiver-keyed view the fast path consumes),
              ``query`` frames run prefill + greedy decode against it and
              answer with a ``tokens`` frame, ``shutdown`` ends the session.
  kv_client — owns the SENDER model.  Exports KV for a context batch,
              pushes the selected layers through ``send_shared`` (exactly
              the SerializedTransport payload, framed), then streams query
              batches and collects the generated tokens.

CLI::

  # terminal 1 — the receiver process (prints "PORT <p>" once listening)
  PYTHONPATH=src python -m repro.launch.remote_serve server --port 0

  # terminal 2 — the sender process
  PYTHONPATH=src python -m repro.launch.remote_serve client --port <p>

``examples/remote_pair.py`` orchestrates both halves and checks the remote
predictions bit-for-bit against an in-process ``InMemoryTransport`` run.
"""
from __future__ import annotations

import argparse
import contextlib
import socket
import sys
import threading
from typing import Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from repro.comm.agent import Agent
from repro.comm.remote import (ChannelClosedError, RemoteChannel,
                               RemoteProtocolError, SocketChannel,
                               build_health_meta, encode_frame, read_frame,
                               send_shared)
from repro.core.types import KVCommConfig, SharedKV

# how many resident page IDs a health_ack ships as the affinity signal
# (newest-touched first to go; see ``PageStore.resident_ids``) — bounds the
# probe frame even against a huge pool
HEALTH_PAGE_IDS_LIMIT = 4096


# ---------------------------------------------------------------------------
# server half (receiver side)
# ---------------------------------------------------------------------------
def serve_channel(agent: Agent, channel: RemoteChannel, store=None, *,
                  lock=None,
                  health_extra: Optional[Callable[[], Dict]] = None) -> int:
    """The receiver-side protocol loop, channel-agnostic (tests drive it
    over a loopback).  A clean peer close ends the loop; a *mid-frame*
    disconnect or corrupt frame propagates as the typed
    ``RemoteProtocolError`` — the server never answers from a half-decoded
    prefix.  Returns the number of query frames answered.

    With a ``store`` (``repro.store.PageStore``) attached the loop also
    speaks the paged wire: ``page_query`` frames are answered with the
    pool's missing-page set and the matching ``page_data`` frame installs
    the materialized prefix — the content-addressed cache server.  The
    installed prefix's block table stays pinned (its pages cannot be
    evicted out from under in-flight queries) until the next paged
    transfer replaces it.

    ``lock`` (any context manager) serializes FRAME HANDLING, not frame
    reads: a concurrent server hands every connection its shared lock, so
    two clients' model/store work never interleaves, while a stalled
    client blocks only its own read — never the fleet (the head-of-line
    fix ``KVServer.serve`` relies on).  ``health_extra`` supplies the
    server-level routing signals (queue depth, slot occupancy) folded
    into the v2 ``health_ack`` payload.

    Streaming installs (``kv_stream_begin``/``chunk``/``end``) feed a
    per-connection ``KVStreamAssembler``; the decoded prefix replaces the
    installed one only when the END frame lands with full coverage, so a
    client dying (or retrying under a fresh stream id) mid-stream leaves
    the previously installed prefix untouched — chunk replay is
    idempotent."""
    from repro.comm.remote import KVStreamAssembler, decode_kv_transfer
    paged_rx = pinned = None
    if store is not None:
        from repro.store.wire import PagedReceiver
        paged_rx = PagedReceiver(store)
    guard = lock if lock is not None else contextlib.nullcontext()
    assembler = KVStreamAssembler()
    shared: Optional[SharedKV] = None
    answered = 0
    try:
        while True:
            try:
                kind, meta, arrays = read_frame(channel)
            except ChannelClosedError:
                break              # peer hung up between frames: clean end
            if kind == "shutdown":
                break
            with guard:
                if kind == "shared_kv":
                    shared, _ = decode_kv_transfer(meta, arrays)
                elif kind in ("kv_stream_begin", "kv_stream_chunk",
                              "kv_stream_end"):
                    done = assembler.feed(kind, meta, arrays)
                    if done is not None:
                        shared, _ = done
                elif kind == "page_query" and paged_rx is not None:
                    channel.write(paged_rx.handle_query(meta, arrays))
                elif kind == "page_data" and paged_rx is not None:
                    shared, table, _, _ = paged_rx.handle_data(meta, arrays)
                    if pinned is not None:
                        store.release(pinned)
                    pinned = table
                elif kind == "health":
                    # liveness + state probe: answers even with no prefix
                    # installed, so clients (and circuit breakers) can tell
                    # a live-but-idle server from a dead one.  The v2
                    # payload carries the routing signals the fabric's
                    # affinity scorer consumes; old clients simply ignore
                    # the extra keys (and old servers' v1 payloads parse
                    # fine — see ``remote.parse_health_meta``).
                    pool = page_ids = None
                    if store is not None:
                        import dataclasses
                        pool = dataclasses.asdict(store.stats())
                        page_ids = store.resident_ids(
                            limit=HEALTH_PAGE_IDS_LIMIT)
                    extra = health_extra() if health_extra is not None \
                        else {}
                    channel.write(encode_frame(
                        "health_ack",
                        build_health_meta(
                            answered=answered,
                            prefix_installed=shared is not None,
                            pool=pool, page_ids=page_ids, **extra), {}))
                elif kind == "query":
                    if shared is None:
                        # answering from no prefix would be confidently
                        # wrong, not an error the client could see —
                        # refuse loudly
                        raise RemoteProtocolError(
                            "query frame before any shared_kv frame")
                    tokens = np.asarray(arrays["tokens"], np.int32)
                    max_new = int(meta.get("max_new", 1))
                    toks, _ = agent.generate(tokens, shared,
                                             max_new=max_new)
                    channel.write(encode_frame(
                        "tokens", {},
                        {"tokens": np.asarray(toks, np.int32)}))
                    answered += 1
                else:
                    raise RemoteProtocolError(
                        f"unexpected frame kind {kind!r}")
    finally:
        # error paths (mid-frame disconnect, corrupt frame, a raising
        # handler) must release the installed prefix too, or every dead
        # connection leaks a pinned table into the pool
        if pinned is not None:
            with guard:
                store.release(pinned)
    return answered


class _CountingLock:
    """An RLock that counts current DEMAND (holders + waiters).  The
    server's health probe reports it as queue depth: how many connection
    handlers want the serve lock right now — the work the server has not
    gotten to yet (minus the probing handler itself)."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._guard = threading.Lock()
        self._demand = 0

    @property
    def demand(self) -> int:
        with self._guard:
            return self._demand

    def __enter__(self) -> "_CountingLock":
        with self._guard:
            self._demand += 1
        self._lock.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self._lock.release()
        with self._guard:
            self._demand -= 1


class KVServer:
    """Serves ONE receiver agent over the frame protocol.  The listener is
    bound at construction (so ``port`` is known before the client dials);
    ``serve_once`` accepts a single connection and serves it to shutdown.

    ``serve``/``start`` run a CONCURRENT accept loop: every accepted
    connection gets its own handler thread, with frame HANDLING (model +
    store work) serialized under one shared lock while frame READS stay
    per-thread — a slow or stalled client holds nothing, so it can never
    head-of-line-block the other connections (the fleet requirement the
    serving fabric routes over).  ``start``/``stop`` are the fabric's
    replica lifecycle: a background accept loop that keeps admitting
    clients until stopped (kill) and can be rebuilt on the same port
    (restart)."""

    def __init__(self, agent: Agent, host: str = "127.0.0.1",
                 port: int = 0, store=None, max_conns: int = 8) -> None:
        self.agent = agent
        self.store = store   # repro.store.PageStore: the evicting pool the
                             # paged wire dedups against across connections
        self.max_conns = max_conns
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(max_conns)
        self.host, self.port = self._listener.getsockname()[:2]
        self._lock = _CountingLock()        # serializes frame handling
        self._guard = threading.Lock()      # guards the bookkeeping below
        self._conns: Set[socket.socket] = set()
        self._threads: List[threading.Thread] = []
        self._accept_thread: Optional[threading.Thread] = None
        self._stopping = False
        self.answered_total = 0             # query frames across all conns

    # -- health signals ------------------------------------------------------
    def _health_extra(self) -> Dict:
        """The server-level routing signals a v2 health_ack carries:
        queue depth (handlers wanting the serve lock, the probing one
        excluded) and slot occupancy (live connections / max)."""
        with self._guard:
            occupied = len(self._conns)
        return {"queue_depth": max(0, self._lock.demand - 1),
                "slots_capacity": self.max_conns,
                "slots_occupied": occupied}

    # -- connection handling -------------------------------------------------
    def _handle(self, sock: socket.socket) -> int:
        try:
            n = serve_channel(self.agent, SocketChannel(sock),
                              store=self.store, lock=self._lock,
                              health_extra=self._health_extra)
            with self._guard:
                self.answered_total += n
            return n
        except RemoteProtocolError as e:
            # one client dying mid-frame must not take the server (and
            # every other client) down with it
            if not self._stopping:
                print(f"[server] connection died: "
                      f"{type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
            return 0
        finally:
            with self._guard:
                self._conns.discard(sock)
            try:
                sock.close()
            except OSError:
                pass

    def _spawn(self, sock: socket.socket) -> threading.Thread:
        with self._guard:
            self._conns.add(sock)
        th = threading.Thread(target=self._handle, args=(sock,),
                              daemon=True)
        th.start()
        return th

    # -- serving modes -------------------------------------------------------
    def serve_once(self, timeout_s: float = 120.0) -> int:
        """Accept one client and serve until it shuts down / disconnects.
        Returns the number of query frames answered."""
        self._listener.settimeout(timeout_s)
        sock, _ = self._listener.accept()
        try:
            return serve_channel(self.agent, SocketChannel(sock),
                                 store=self.store)
        finally:
            sock.close()
            self._listener.close()

    def serve(self, conns: int, timeout_s: float = 120.0) -> int:
        """Accept ``conns`` clients over the same listener, each served on
        its OWN thread — connections interleave, so a slow client never
        blocks the others; the page pool is shared (a later client's
        ``page_query`` dedups against every earlier client's pages — the
        cross-request cache) and its mutation is serialized under the
        frame-handling lock.

        Protocol errors poison only their own connection (logged, the
        rest keep going); ``serve_once`` keeps the strict
        single-connection semantics.  Returns the total number of query
        frames answered once every accepted connection completes."""
        self._listener.settimeout(timeout_s)
        threads = []
        try:
            for _ in range(conns):
                sock, _ = self._listener.accept()
                threads.append(self._spawn(sock))
        finally:
            for th in threads:
                th.join()
            self._listener.close()
        return self.answered_total

    def start(self, poll_s: float = 0.05) -> None:
        """Run the accept loop in a background thread until ``stop`` —
        the fabric's replica lifecycle (a ``serve`` with no connection
        quota)."""
        if self._accept_thread is not None:
            raise RuntimeError("server already started")
        self._stopping = False
        self._listener.settimeout(poll_s)

        def loop() -> None:
            while not self._stopping:
                try:
                    sock, _ = self._listener.accept()
                except socket.timeout:
                    continue
                except OSError:
                    break          # listener closed under us: stop()
                self._threads.append(self._spawn(sock))

        self._accept_thread = threading.Thread(target=loop, daemon=True)
        self._accept_thread.start()

    def stop(self, timeout_s: float = 5.0) -> None:
        """Kill the replica: stop accepting, sever every live connection
        (their handlers release any pinned block table on the way out —
        no pin outlives a dead connection), and join the handler
        threads.  Idempotent; a stopped server's port can be re-bound by
        a fresh ``KVServer`` (the restart half of a chaos schedule)."""
        self._stopping = True
        try:
            self._listener.close()
        except OSError:
            pass
        with self._guard:
            conns = list(self._conns)
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=timeout_s)
            self._accept_thread = None
        for th in self._threads:
            th.join(timeout=timeout_s)
        self._threads.clear()


# ---------------------------------------------------------------------------
# client half (sender side)
# ---------------------------------------------------------------------------
def export_pages(sender: Agent, context: np.ndarray, kvcfg: KVCommConfig,
                 select, *, page_len: int = 16,
                 wire_dtype: str = "float16"):
    """Export the sender's selected KV over ``context`` and split it into
    content-addressed pages — the sender-side half of a paged share,
    WITHOUT any wire exchange.  Returns ``(table, pages, states,
    state_select)``.  The serving fabric calls this once per request so
    the router can score replicas by page-id overlap before a single
    byte ships; ``KVClient.share_pages`` then ships the result."""
    from repro import core
    from repro.core.protocol import gather_selected
    from repro.store.paging import split_payload
    import jax.numpy as jnp
    kv, states, _ = sender.export_kv(context)
    state_select = None
    if states is not None:
        import jax
        n_ssm = jax.tree.leaves(states)[0].shape[0]
        state_select = np.ones((n_ssm,), bool)
    payload = gather_selected(kv, jnp.asarray(select))
    table, pages = split_payload(
        payload, layers=core.selected_layer_ids(select),
        select=np.asarray(select), page_len=page_len,
        wire_dtype=wire_dtype, pos_mode=kvcfg.pos_mode)
    return table, pages, states, state_select


class KVClient:
    """The sender-side handle on a remote receiver.

    With a ``policy`` (``repro.comm.resilience.RetryPolicy``) attached,
    every operation retries under it; when the client also knows HOW to
    re-dial (``channel_factory``, set automatically by ``connect``), a
    retry reconnects first, and operations that need the installed prefix
    (``generate``) replay the last successful share before retrying — the
    idempotent resend.  A replayed PAGED share re-runs the dedup handshake
    against the server's pool, so a same-server reconnect ships ~zero
    pages: retry bytes stay bounded by what the pool is actually
    missing."""

    def __init__(self, channel: RemoteChannel, *,
                 channel_factory=None, policy=None) -> None:
        self.channel = channel
        self.channel_factory = channel_factory
        self.policy = policy
        self.sent_bytes = 0
        self._xid = 0
        self._sid = 0          # stream id: fresh per streamed share try
        self._reshare = None   # replays the last successful share

    @classmethod
    def connect(cls, host: str, port: int, timeout_s: float = 30.0, *,
                policy=None, io_timeout_s: Optional[float] = None
                ) -> "KVClient":
        def factory():
            return SocketChannel.connect(host, port, timeout_s=timeout_s,
                                         io_timeout_s=io_timeout_s)
        return cls(factory(), channel_factory=factory, policy=policy)

    # -- retry plumbing -----------------------------------------------------
    def _reconnect(self, replay: bool) -> None:
        try:
            self.channel.close()
        except (RemoteProtocolError, OSError):
            pass
        self.channel = self.channel_factory()
        if replay and self._reshare is not None:
            # a fresh connection (possibly a restarted server) holds no
            # prefix — reinstall it before replaying the failed op
            self._reshare()

    def _with_retry(self, fn, describe: str, replay: bool):
        if self.policy is None:
            return fn()

        def wrapped(attempt: int):
            if attempt > 0 and self.channel_factory is not None:
                self._reconnect(replay)
            return fn()

        return self.policy.run(wrapped, describe=describe)

    # -- operations ---------------------------------------------------------
    def share(self, sender: Agent, context: np.ndarray,
              kvcfg: KVCommConfig, select, *, wire_dtype="float16",
              packed: bool = True,
              chunk_bytes: Optional[int] = None) -> int:
        """Export the sender's KV over ``context`` and ship the selected
        layers; the server installs the decoded view as the current prefix.
        ``chunk_bytes`` streams the transfer in bounded
        begin/chunk/end frames (the server decodes each chunk as it
        lands, overlapping the client's encode of the next one); ``None``
        keeps the single monolithic frame.  A retried streamed share
        restarts under a FRESH stream id — the server installs nothing
        until an end frame completes, so replay is idempotent.  Returns
        (and accumulates) the payload wire bytes."""
        def once():
            return self._share_once(sender, context, kvcfg, select,
                                    wire_dtype, packed, chunk_bytes)
        n = self._with_retry(once, "remote share", replay=False)
        self._reshare = once
        return n

    def _share_once(self, sender, context, kvcfg, select, wire_dtype,
                    packed, chunk_bytes=None) -> int:
        kv, states, _ = sender.export_kv(context)
        state_select = None
        if states is not None:
            import jax
            n_ssm = jax.tree.leaves(states)[0].shape[0]
            state_select = np.ones((n_ssm,), bool)
        sid, self._sid = self._sid, self._sid + 1
        n = send_shared(self.channel, kvcfg, kv, select, states=states,
                        state_select=state_select, wire_dtype=wire_dtype,
                        packed=packed, chunk_bytes=chunk_bytes, sid=sid)
        self.sent_bytes += n
        return n

    def share_paged(self, sender: Agent, context: np.ndarray,
                    kvcfg: KVCommConfig, select, *, page_len: int = 16,
                    wire_dtype: str = "float16") -> Tuple[int, int, int]:
        """Dedup-aware share: split the selected KV into content-addressed
        pages, ask the server's pool which it is missing (``page_query`` ->
        ``page_need``), and ship ONLY those (``page_data``).  The sender
        needs no pool of its own — the server's ``PageStore`` is the single
        source of residency truth.  Returns ``(payload_bytes, pages_total,
        pages_sent)``; payload bytes (novel pages + int8 scales + states)
        accumulate on ``sent_bytes``."""
        def once():
            return self._share_paged_once(sender, context, kvcfg, select,
                                          page_len, wire_dtype)
        out = self._with_retry(once, "paged remote share", replay=False)
        self._reshare = once
        return out

    def _share_paged_once(self, sender, context, kvcfg, select, page_len,
                          wire_dtype) -> Tuple[int, int, int]:
        table, pages, states, state_select = export_pages(
            sender, context, kvcfg, select, page_len=page_len,
            wire_dtype=wire_dtype)
        return self._share_pages_once(table, pages, wire_dtype, states,
                                      state_select)

    def share_pages(self, table, pages, *, wire_dtype: str = "float16",
                    states=None, state_select=None) -> Tuple[int, int, int]:
        """Ship an ALREADY-SPLIT page set (``repro.store.split_payload`` /
        ``export_pages``) through the dedup handshake — the serving
        fabric's entry point: the router splits once to score replicas by
        page-id overlap, then ships the same table/pages to whichever
        replica won.  Same retry/replay semantics as ``share_paged``."""
        def once():
            return self._share_pages_once(table, pages, wire_dtype,
                                          states, state_select)
        out = self._with_retry(once, "paged remote share", replay=False)
        self._reshare = once
        return out

    def _share_pages_once(self, table, pages, wire_dtype, states,
                          state_select) -> Tuple[int, int, int]:
        from repro.store.wire import (decode_page_need, encode_page_data,
                                      encode_page_query)
        xid, self._xid = self._xid, self._xid + 1
        self.channel.write(encode_page_query(xid, table))
        kind, meta, _ = read_frame(self.channel)
        if kind != "page_need":
            raise RemoteProtocolError(f"expected a page_need frame, "
                                      f"got {kind!r}")
        _, need = decode_page_need(meta)
        by_id = {p.page_id: p for p in pages}
        frame, n = encode_page_data(
            xid, [by_id[pid] for pid in need], wire_dtype=wire_dtype,
            states=states, state_select=state_select)
        self.channel.write(frame)
        n += table.scale_nbytes
        self.sent_bytes += n
        return n, table.num_pages, len(need)

    def generate(self, query: np.ndarray, max_new: int = 1) -> np.ndarray:
        """Ask the remote receiver to answer ``query`` (B, Sq) against the
        last shared prefix; returns the (B, max_new) generated tokens."""
        def once():
            self.channel.write(encode_frame(
                "query", {"max_new": int(max_new)},
                {"tokens": np.asarray(query, np.int32)}))
            kind, _, arrays = read_frame(self.channel)
            if kind != "tokens":
                raise RemoteProtocolError(f"expected a tokens frame, "
                                          f"got {kind!r}")
            return np.asarray(arrays["tokens"], np.int32)
        return self._with_retry(once, "remote generate", replay=True)

    def probe(self) -> dict:
        """Health-check the server: one ``health`` frame round trip.
        Returns the server's status meta ({"answered", "prefix_installed",
        "pool"}); raises the usual typed errors when the peer is gone —
        feed the outcome to a ``CircuitBreaker``."""
        def once():
            self.channel.write(encode_frame("health", {}, {}))
            kind, meta, _ = read_frame(self.channel)
            if kind != "health_ack":
                raise RemoteProtocolError(f"expected a health_ack frame, "
                                          f"got {kind!r}")
            return meta
        return self._with_retry(once, "health probe", replay=False)

    def close(self) -> None:
        try:
            self.channel.write(encode_frame("shutdown", {}, {}))
        except (RemoteProtocolError, OSError):
            pass
        self.channel.close()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _load_agents(args) -> Tuple[Agent, Agent, object]:
    from repro.launch.pairs import load_pair
    cfg, tok, sender, receiver = load_pair(args.arch)
    return (Agent("sender", cfg, sender, tok),
            Agent("receiver", cfg, receiver, tok), tok)


def run_server(args) -> None:
    _, receiver, _ = _load_agents(args)
    store = None
    if args.pool_mb > 0:
        from repro.store import PageStore
        store = PageStore(page_len=args.page_len,
                          capacity_bytes=args.pool_mb * (1 << 20))
    server = KVServer(receiver, host=args.host, port=args.port,
                      store=store)
    # the orchestrating parent (examples/remote_pair.py) reads this line
    # to learn the bound port before dialing
    print(f"PORT {server.port}", flush=True)
    if args.serve_conns > 1:
        answered = server.serve(args.serve_conns, timeout_s=args.timeout)
    else:
        answered = server.serve_once(timeout_s=args.timeout)
    print(f"[server] answered {answered} query frames", flush=True)
    if store is not None:
        st = store.stats()
        print(f"[server] pool: {st.pages} pages, {st.used_bytes} bytes, "
              f"hit_rate {st.hit_rate:.3f}, {st.evictions} evictions",
              flush=True)


def run_client(args) -> None:
    from repro.data.synthetic import SyntheticTask, TaskConfig
    sender, _, tok = _load_agents(args)
    task = SyntheticTask(tok, TaskConfig("retrieval", num_facts=6, seed=42))
    batch = task.batch(args.requests)
    kvcfg = KVCommConfig(ratio=args.ratio, selector="prior_only")
    from repro import core
    select = core.make_selection(sender.cfg, kvcfg)
    policy = None
    if args.retries > 1:
        from repro.comm.resilience import RetryPolicy
        policy = RetryPolicy(max_attempts=args.retries)
    client = KVClient.connect(args.host, args.port, policy=policy,
                              io_timeout_s=args.io_timeout)
    try:
        if args.paged:
            n, total, sent = client.share_paged(
                sender, batch["context"], kvcfg, select,
                page_len=args.page_len, wire_dtype=args.wire_dtype)
            print(f"[client] paged: {sent}/{total} pages shipped "
                  f"({total - sent} pool hits)")
        else:
            n = client.share(sender, batch["context"], kvcfg, select,
                             wire_dtype=args.wire_dtype,
                             chunk_bytes=(args.chunk_kb * 1024
                                          if args.chunk_kb > 0 else None))
        toks = client.generate(batch["query"], max_new=1)
    finally:
        client.close()
    acc = float(np.mean(toks[:, 0] == batch["answer"]))
    print(f"[client] shipped {n} payload bytes, accuracy {acc:.3f}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="role", required=True)
    s = sub.add_parser("server", help="receiver-side KV server")
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=0,
                   help="0 picks a free port (printed as 'PORT <p>')")
    s.add_argument("--timeout", type=float, default=120.0)
    s.add_argument("--pool-mb", type=int, default=0,
                   help=">0 attaches a content-addressed page pool of this "
                        "capacity — the server answers the paged wire and "
                        "dedups repeat prefixes against it")
    s.add_argument("--page-len", type=int, default=16)
    s.add_argument("--serve-conns", type=int, default=1,
                   help="accept this many sequential client connections; "
                        "the page pool persists across them (a later "
                        "client's shares dedup against earlier clients')")
    c = sub.add_parser("client", help="sender-side KV client")
    c.add_argument("--host", default="127.0.0.1")
    c.add_argument("--port", type=int, required=True)
    c.add_argument("--requests", type=int, default=8)
    c.add_argument("--ratio", type=float, default=0.5)
    c.add_argument("--wire-dtype", default="float16",
                   help="float16 | bfloat16 | float32 | int8 | int4, or "
                        "an adaptive per-layer 'plan:<dtype,dtype,...>' "
                        "spec with one entry per selected layer")
    c.add_argument("--chunk-kb", type=int, default=0,
                   help=">0 streams the (unpaged) share in frames of "
                        "roughly this many KiB instead of one monolithic "
                        "frame, so the server decodes while the client "
                        "still encodes")
    c.add_argument("--paged", action="store_true",
                   help="ship via the dedup-aware paged wire (the server "
                        "must run with --pool-mb > 0)")
    c.add_argument("--page-len", type=int, default=16)
    c.add_argument("--retries", type=int, default=1,
                   help=">1 retries failed operations under a RetryPolicy "
                        "with that many attempts, reconnecting (and "
                        "replaying the share before a generate) between "
                        "tries")
    c.add_argument("--io-timeout", type=float, default=None,
                   help="per-read/write socket timeout in seconds (raises "
                        "the typed ChannelTimeoutError instead of hanging "
                        "on a stalled peer)")
    for p in (s, c):
        p.add_argument("--arch", default=None,
                       help="registry config at full width with seeded "
                            "weights (both sides must agree); default: the "
                            "trained CPU stand-in pair")
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.role == "server":
        run_server(args)
    else:
        run_client(args)


if __name__ == "__main__":
    main()
