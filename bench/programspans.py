"""The program's own host spans in a profiler trace.

The served path writes ``kvcomm.*`` spans (``repro.utils.spans``) on the
host plane ``/host:CPU``, on the clock of the device planes, some with
stats (``rid``).  ``tracereduce.reduce_planes`` keeps only the harness's
``bench.*`` spans, so that every number it feeds stays as it was;
``collect`` keeps the program's, and ``attach`` hangs them on a reduced
``Trace`` as ``program_spans``, where the readers of
``wire_encode_stall_ms``, ``wire_decode_stall_ms`` and
``sched_host_stall_ms`` look for them.  A trace without them (a program
that writes none, or a ``Trace`` nobody attached them to) reads as
nothing, never as zero.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import tracereduce

PREFIX = "kvcomm."
SHARE = "kvcomm.share"
SCHED_STEP = "kvcomm.sched.step"
# the scheduler's own host phases around each ragged step
SCHED_HOST = ("kvcomm.sched.retire", "kvcomm.sched.step",
              "kvcomm.sched.read", "kvcomm.sched.poll")


def collect(planes) -> List[tuple]:
    """(start, end, name, stats) of every ``kvcomm.*`` host event, in
    start order; ``stats`` is a dict, empty where the event has none."""
    out = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append((s, s + int(ev.duration_ns), ev.name,
                                dict(getattr(ev, "stats", None) or ())))
    out.sort(key=lambda x: (x[0], -x[1]))
    return out


def attach(trace, planes):
    trace.program_spans = collect(planes)
    return trace


def events(trace, *names) -> Optional[List[Tuple[int, int]]]:
    """(start, end) of the program spans named ``names`` inside the traced
    window; None where the trace carries no program spans."""
    spans = getattr(trace, "program_spans", None)
    if not spans:
        return None
    return [(s, e) for s, e, n, _ in spans
            if n in names and e > trace.t0 and s < trace.t1]


def stall_ms(trace, names, per: str) -> Optional[float]:
    """Device idle inside the spans ``names``, in ms per span ``per``."""
    inside, count = events(trace, *names), events(trace, per)
    if not count:
        return None
    return trace.idle_within(inside) / len(count) * 1e3


def coverage(trace) -> Optional[float]:
    """Share of the device's idle time inside the harness's waves that
    lies inside some program span."""
    inside = events(trace, *{n for _, _, n, _ in
                             getattr(trace, "program_spans", None) or ()})
    waves = trace.span_events("bench.wave")
    if not inside or not waves:
        return None
    idle = trace.idle_within(waves)
    return trace.idle_within(inside) / idle if idle > 0 else None


def relabelled(trace):
    """The reduced trace again with the program's spans in place of the
    harness's inner ones, so that its ``breakdown()`` puts each idle gap
    down to the innermost program span over it ("bench.wave" where none
    is)."""
    spans = [(s, e, n) for s, e, n, _ in trace.program_spans]
    waves = [w + ("bench.wave",) for w in trace.span_events("bench.wave")]
    return tracereduce.Trace(trace.devices, spans + waves)
