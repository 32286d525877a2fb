"""Set-up and measured window of one cell, on the program's served path.

The window drives ``repro.serving.scheduler.Scheduler.run`` as
``repro.launch.serve`` builds it: a ``CommSession`` of a sender and a
receiver ``Agent`` sharing one parameter tree over an int8
``SerializedTransport``, one calibration under a task key, and a
``Scheduler`` with the Pallas decode backend.  It runs closed waves back to
back until ``seconds`` have passed; the wave in flight then finishes and
counts.  A cell's ``store`` (``PageStore`` arguments) attaches the
program's paged prefix store to the transport.

With tracing on, thin wrappers around the session's ``share``, the
receiver's ``prefill`` and ``ragged_step`` and the sender's ``export_kv``
write host spans into the profiler's trace and record the per-row lengths
of every decode step.  The program is not edited; the wrappers sit on the
benchmark's own instances.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import cells
import weights

CALIB_KEY = "bench"
WARM_TAG = 1 << 40          # token-id stream tags of the set-up waves


class Tok:
    """The two token ids the program's agents need."""

    def __init__(self, bos: int, pad: int):
        self.BOS, self.PAD = bos, pad


@dataclasses.dataclass
class Wave:
    requests: list
    completions: list
    stats: dict
    start: float
    end: float


class CompileWatch:
    """Counts every executable made, by a backend compile or a load from
    the persistent cache, and every trace of the program's jitted entry
    points."""

    def __init__(self):
        from repro.core.protocol import TRACE_COUNTS
        self._traces = TRACE_COUNTS
        self.compiled = self.loaded = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def _duration(self, name, secs, **_):
        # also reported for an executable loaded from the cache
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def snapshot(self):
        return self.compiled, sum(self._traces.values())


class Bench:
    def __init__(self, cell: dict, seed: int):
        self.cell, self.seed = cell, seed
        self.conf = cell["config_file"]
        self.vocab = self.conf["vocab_size"]
        self.sizes = cells.wave_sizes(cell["traffic_file"], cell["wave"])
        self.steps: List[tuple] = []
        self.tracing = False

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        from repro.comm import Agent, CommSession, SerializedTransport
        from repro.core.types import KVCommConfig
        from repro.serving.scheduler import Scheduler, SchedulerConfig
        from repro.store import PageStore
        c, conf = self.cell, self.conf
        t = time.perf_counter()
        self.cfg = cells.family(conf).model_config(conf)
        self.params = weights.make(conf, conf["weights_seed"])
        jax.block_until_ready(self.params)
        self.phases = {"weights_s": time.perf_counter() - t}
        tok = Tok(conf["bos_token_id"], conf["pad_token_id"])
        self.session = CommSession(
            Agent("sender", self.cfg, self.params, tok),
            Agent("receiver", self.cfg, self.params, tok),
            SerializedTransport(c["wire"], store=PageStore(**c["store"])
                                if "store" in c else None))
        ctx, qry = cells.calibration_sample(conf)
        self.session.calibrate(ctx[None], qry[None], key=CALIB_KEY)
        self.phases["calibrate_s"] = time.perf_counter() - t
        self.kvcfg = KVCommConfig(ratio=c["ratio"], alpha=c["alpha"])
        self.sched = Scheduler(
            self.session, self.kvcfg, calib_key=CALIB_KEY,
            config=SchedulerConfig(capacity=c["capacity"],
                                   prefix_bucket=c["prefix_bucket"],
                                   query_bucket=c["query_bucket"],
                                   decode_backend=c["decode_backend"]))
        self.layers = tuple(self.sched.layers)
        self._snap = jax.jit(lambda a: a + 0)
        self._snap(jnp.zeros((c["capacity"],), jnp.int32)).block_until_ready()
        # every shape the window uses
        for i, sizes in enumerate(cells.warm_waves(c)):
            self.sched.run(cells.requests(sizes, self.vocab, self.seed,
                                          WARM_TAG + i))
            self.phases[f"warm_wave{i}_s"] = time.perf_counter() - t

    # -- the window ----------------------------------------------------------
    def wave_requests(self, k: int):
        return cells.requests(self.sizes, self.vocab, self.seed, k,
                              first_rid=k * len(self.sizes))

    def window(self, seconds: float) -> List[Wave]:
        waves: List[Wave] = []
        self.wire0 = self.session.transport.total_bytes
        t0 = time.perf_counter()
        while not waves or waves[-1].end - t0 < seconds:
            reqs = self.wave_requests(len(waves))
            start = time.perf_counter()
            if self.tracing:
                with jax.profiler.TraceAnnotation("bench.wave"):
                    comps, stats = self.sched.run(reqs)
            else:
                comps, stats = self.sched.run(reqs)
            waves.append(Wave(reqs, comps, stats, start,
                              time.perf_counter()))
        self.t0 = t0
        return waves

    # -- tracing -------------------------------------------------------------
    def trace_on(self) -> None:
        """Wrap the calls into each layer with host spans, and record each
        decode step's per-row lengths for the roofline."""
        sess, rx, tx = self.session, self.session.receiver, \
            self.session.sender
        self.tracing = True

        def span(name, fn, record=None):
            def wrapped(*a, **k):
                if record is not None:
                    record(*a, **k)
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **k)
            return wrapped

        def step_rows(tokens, cache, shared, prefix_lens, active, **_):
            self.steps.append((self._snap(cache["len"]), prefix_lens,
                               active))

        sess.share = span("bench.share", sess.share)
        tx.export_kv = span("bench.sender_prefill", tx.export_kv)
        rx.prefill = span("bench.admit_prefill", rx.prefill)
        rx.ragged_step = span("bench.decode_step", rx.ragged_step, step_rows)

    def step_rows(self):
        """Per decode step: (own keys per row, real prefix per row, live
        rows), own keys counting the step's new token."""
        dst = max(s.prefix for s in self.sizes)
        dst = -(-dst // self.cell["prefix_bucket"]) * self.cell["prefix_bucket"]
        out = []
        for lens, pfx, act in self.steps:
            own = np.asarray(lens) - dst + 1
            out.append((own, np.asarray(pfx), np.asarray(act)))
        return out
