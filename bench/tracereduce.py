"""From a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU the device planes are ``/device:TPU:<n>``, with a line
``XLA Modules`` (one event per executed program, named
``jit_<function>(<hash>)``) and a line ``XLA Ops`` (one event per executed
HLO instruction, named by its text, ``%<name> = <shape> <opcode>(...)``;
a loop's body ops nest inside the loop's event).  The harness's host spans
(``bench.*``, from ``jax.profiler.TraceAnnotation``) are on the host plane
``/host:CPU``, on the same clock.

* busy time: the union of the ``XLA Ops`` intervals inside the traced
  window (the first ``bench.wave`` span's start to the last one's end),
  averaged over the chips;
* a program's device time: the sum of its ``XLA Modules`` events;
* the Pallas kernels: ``XLA Ops`` events whose text has
  ``custom_call_target="tpu_custom_call"``;
* idle gaps: the holes in the busy union, each labelled with the innermost
  harness span that covers its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_MODULE = re.compile(r"^(.*)\((\d+)\)$")
_INSTR = re.compile(r"^%?([^\s=]+)")


@dataclasses.dataclass
class Op:
    start: int
    end: int
    name: str
    module: str
    kernel: bool
    self_ns: int = 0


def _module_name(event_name: str) -> str:
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def _merge(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class Trace:
    """The reduced trace of one traced window."""

    def __init__(self, devices: Dict[str, dict], spans: List[tuple]):
        self.spans = sorted(spans)                      # (start, end, name)
        self._inner = [x for x in self.spans if x[2] != "bench.wave"]
        self._starts = [x[0] for x in self._inner]
        waves = [s for s in self.spans if s[2] == "bench.wave"]
        if not waves:
            raise ValueError("no bench.wave span in the trace")
        self.t0 = min(s[0] for s in waves)
        self.t1 = max(s[1] for s in waves)
        self.window_s = (self.t1 - self.t0) / 1e9
        self.devices = devices
        busy = []
        for dev in devices.values():
            dev["busy"] = _merge(_clip([(o.start, o.end) for o in dev["ops"]],
                                       self.t0, self.t1))
            busy.append(sum(e - s for s, e in dev["busy"]))
        self.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0

    # -- programs and kernels ------------------------------------------------
    def module_events(self, name: str) -> List[Tuple[int, int]]:
        """(start, end) of every execution of the programs whose name
        contains ``name``, inside the window, on every chip."""
        return [(s, e) for dev in self.devices.values()
                for s, e, m in dev["modules"]
                if name in m and e > self.t0 and s < self.t1]

    def module_seconds(self, name: str) -> Tuple[float, int]:
        ev = self.module_events(name)
        return sum(e - s for s, e in ev) / 1e9, len(ev)

    def kernel_ops(self, module: str) -> List[Op]:
        return [o for dev in self.devices.values() for o in dev["ops"]
                if o.kernel and module in o.module
                and o.end > self.t0 and o.start < self.t1]

    # -- host spans ------------------------------------------------------------
    def span_events(self, name: str) -> List[Tuple[int, int]]:
        return [(s, e) for s, e, n in self.spans if n == name
                and e > self.t0 and s < self.t1]

    def idle_within(self, intervals) -> float:
        """Seconds, averaged over the chips, in which no op ran inside the
        given (start, end) intervals."""
        iv = _merge(intervals)
        total = sum(e - s for s, e in iv)
        idle = []
        for dev in self.devices.values():
            covered = 0
            for s, e in iv:
                covered += sum(b - a for a, b in _clip(dev["busy"], s, e))
            idle.append(total - covered)
        return sum(idle) / len(idle) / 1e9 if idle else 0.0

    def _label(self, t: int, depth: int = 64) -> str:
        """The innermost span covering ``t``: spans nest, so it is among
        the few that started last before ``t``; waves are checked last."""
        i = bisect.bisect_right(self._starts, t)
        best = None
        for s, e, n in self._inner[max(0, i - depth):i][::-1]:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        if best:
            return best[2]
        covered = any(s <= t <= e for s, e, n in self.spans
                      if n == "bench.wave")
        return "bench.wave" if covered else "outside spans"

    # -- the breakdown -------------------------------------------------------------
    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops: Dict[str, float] = defaultdict(float)
        gaps: Dict[str, float] = defaultdict(float)
        n = len(self.devices)
        for dev in self.devices.values():
            for o in dev["ops"]:
                if o.end > self.t0 and o.start < self.t1:
                    ops[f"{o.module}:{o.name}"] += o.self_ns / 1e9 / n
            b = dev["busy"]
            edges = [(self.t0, self.t0)] + b + [(self.t1, self.t1)]
            for (_, e0), (s1, _) in zip(edges, edges[1:]):
                if s1 > e0:
                    gaps[self._label((e0 + s1) // 2)] += (s1 - e0) / 1e9 / n
        rank = lambda d: [[k, v] for k, v in
                          sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}


def _self_times(ops: List[Op]) -> None:
    """Each op's time less the time of the ops nested inside it."""
    stack: List[Op] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        o.self_ns = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)


def reduce_planes(planes) -> Trace:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them."""
    devices: Dict[str, dict] = {}
    spans: List[tuple] = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            modules, raw = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        modules.append((s, s + int(ev.duration_ns),
                                        _module_name(ev.name)))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        raw.append((s, s + int(ev.duration_ns), ev.name))
            modules.sort()
            starts = [m[0] for m in modules]
            ops = []
            for s, e, text in raw:
                i = bisect.bisect_right(starts, s) - 1
                mod = modules[i][2] if i >= 0 and modules[i][1] >= e else "?"
                m = _INSTR.match(text)
                ops.append(Op(s, e, m.group(1) if m else text[:40], mod,
                              KERNEL_MARK in text))
            _self_times(ops)
            devices[plane.name] = {"modules": modules, "ops": ops}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
    return Trace(devices, spans)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_planes(ProfileData.from_file(paths[-1]).planes)


@dataclasses.dataclass
class Context:
    """What a metric reader gets: the cell's ``bench`` (configuration,
    selection, recorded decode steps), the window's waves, set-up time,
    the device's peaks, and with ``--trace 1`` the reduced trace."""
    bench: object
    waves: list
    setup_s: float
    peak: dict
    trace: Optional[Trace] = None

    @property
    def window_s(self) -> float:
        return self.waves[-1].end - self.bench.t0
