"""Host spans of the served path, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation``: with the profiler off it
costs about a microsecond and records nothing; with it on, the span lands
on the host plane of the trace beside the device's ops, so an idle gap on
the chip can be put down to the host phase that held it.  Keyword stats
(``rid``) arrive as the event's stats.  Spans belong in host code only,
never inside a jitted function (there they would time the trace, once).

Nesting on the scheduler's path::

    kvcomm.sched.setup
    kvcomm.sched.retire
    kvcomm.sched.admit (rid)
        kvcomm.share (rid)
            kvcomm.sender.prefill
            kvcomm.wire.encode / kvcomm.wire.channel / kvcomm.wire.decode
        kvcomm.admit.prefill (rid)
        kvcomm.admit.insert (rid)
    kvcomm.sched.step
    kvcomm.sched.read
    kvcomm.sched.poll
    kvcomm.sched.drain
"""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

import jax

PREFIX = "kvcomm."

SCHED_SETUP = "kvcomm.sched.setup"
SCHED_RETIRE = "kvcomm.sched.retire"
SCHED_ADMIT = "kvcomm.sched.admit"
SCHED_STEP = "kvcomm.sched.step"
SCHED_READ = "kvcomm.sched.read"
SCHED_POLL = "kvcomm.sched.poll"
SCHED_DRAIN = "kvcomm.sched.drain"
SHARE = "kvcomm.share"
SENDER_PREFILL = "kvcomm.sender.prefill"
WIRE_ENCODE = "kvcomm.wire.encode"
WIRE_CHANNEL = "kvcomm.wire.channel"
WIRE_DECODE = "kvcomm.wire.decode"
ADMIT_PREFILL = "kvcomm.admit.prefill"
ADMIT_INSERT = "kvcomm.admit.insert"

# the remote transport's TransferRecord fields, stamped over the wire spans
WIRE_FIELDS = {WIRE_ENCODE: "serialize_s", WIRE_CHANNEL: "channel_s",
               WIRE_DECODE: "deserialize_s"}


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of the constants above)."""
    return jax.profiler.TraceAnnotation(name, **stats)


class WireClock:
    """Seconds per wire phase of one transfer, each phase also a span, so
    the transfer record's ``serialize_s`` / ``channel_s`` /
    ``deserialize_s`` cover exactly the ``kvcomm.wire.*`` spans."""

    def __init__(self):
        self.seconds = defaultdict(float)

    @contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        with span(name):
            yield
        self.seconds[name] += time.perf_counter() - t

    def fields(self) -> dict:
        return {f: self.seconds[n] for n, f in WIRE_FIELDS.items()}
