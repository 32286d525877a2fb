"""The served path's spans and counters: every ``kvcomm.*`` span of a
scheduler run lands in the profiler's trace, nested as the path nests and
tagged with its request, the remote transport's wire split times the same
intervals as its spans, and the per-request counters agree with the
loop."""
import dataclasses
import glob
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.comm import Agent, CommSession, RemoteTransport, SerializedTransport
from repro.core.types import KVCommConfig
from repro.data.synthetic import SyntheticTask, TaskConfig
from repro.models import transformer as tfm
from repro.serving.scheduler import Scheduler, SchedulerConfig, make_requests
from repro.store import PageStore
from repro.utils import spans

CAP = 3
KVCFG = KVCommConfig(ratio=0.5, selector="prior_only")
SCHED = {spans.SCHED_SETUP, spans.SCHED_RETIRE, spans.SCHED_ADMIT,
         spans.SCHED_STEP, spans.SCHED_READ, spans.SCHED_POLL,
         spans.SCHED_DRAIN, spans.SHARE, spans.SENDER_PREFILL,
         spans.ADMIT_PREFILL, spans.ADMIT_INSERT}
WIRE = {spans.WIRE_ENCODE, spans.WIRE_CHANNEL, spans.WIRE_DECODE}
TRANSPORTS = {
    # the in-process int8 wire has no channel; the remote ones have all
    # three, streamed or paged
    "serialized": (lambda: SerializedTransport("int8"),
                   WIRE - {spans.WIRE_CHANNEL}),
    "remote": (lambda: RemoteTransport("float32"), WIRE),
    "remote_paged": (lambda: RemoteTransport(
        "float32", store=PageStore(page_len=4)), WIRE),
}


def _requests(tok):
    batches = [SyntheticTask(tok, TaskConfig("retrieval", num_facts=nf,
                                             seed=3 + nf)).batch(3)
               for nf in (4, 8)]
    reqs = make_requests(batches, pad=tok.PAD)
    for i, r in enumerate(reqs):
        # three or more tokens: a slot frees only after the first reads
        r.max_new = (3, 5, 4)[i % 3]
    return reqs


@pytest.fixture(scope="module", params=sorted(TRANSPORTS))
def traced(request, tiny_cfg, tok, tmp_path_factory):
    """One scheduler run under the profiler: (host events, completions,
    the run's transfer records, requests, expected wire spans)."""
    make, wire = TRANSPORTS[request.param]
    cfg = dataclasses.replace(tiny_cfg, vocab_size=tok.vocab_size)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    sess = CommSession(Agent("s", cfg, params, tok),
                       Agent("r", cfg, params, tok), make())
    sched = Scheduler(sess, KVCFG, config=SchedulerConfig(
        capacity=CAP, prefix_bucket=8, query_bucket=4))
    reqs = _requests(tok)
    sched.run(reqs)                        # compile outside the trace
    n_log = len(sess.transport.log)
    out = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        jax.profiler.start_trace(out)
        try:
            comps, _ = sched.run(reqs)
        finally:
            jax.profiler.stop_trace()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name,
               dict(ev.stats))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith(spans.PREFIX)]
    return events, comps, sess.transport.log[n_log:], reqs, wire


def _inside(ev, outer):
    return outer[0] <= ev[0] and ev[1] <= outer[1]


def test_every_span_appears(traced):
    events, _, _, _, wire = traced
    assert {e[2] for e in events} == SCHED | wire


def test_spans_nest_and_carry_their_request(traced):
    events, _, _, reqs, wire = traced
    by = lambda n: [e for e in events if e[2] == n]
    admits, shares = by(spans.SCHED_ADMIT), by(spans.SHARE)
    assert sorted(e[3]["rid"] for e in admits) == sorted(r.rid for r in reqs)
    for name in wire | {spans.SENDER_PREFILL}:
        for e in by(name):
            assert any(_inside(e, s) for s in shares), name
    for name in (spans.SHARE, spans.ADMIT_PREFILL, spans.ADMIT_INSERT):
        for e in by(name):
            assert any(_inside(e, a) and a[3]["rid"] == e[3]["rid"]
                       for a in admits), name


def test_queue_and_admission_times(traced):
    _, comps, _, _, _ = traced
    for c in comps:
        assert 0.0 <= c.queue_s <= c.admitted_s <= c.ttft_s
    # the first CAP requests are admitted before any first token is read;
    # the rest wait for a slot, which frees only after a read
    first_read = min(c.ttft_s for c in comps)
    assert max(c.queue_s for c in comps[:CAP]) < first_read
    assert min(c.queue_s for c in comps[CAP:]) > first_read
    assert comps[0].queue_s < comps[0].admitted_s


@pytest.mark.parametrize("traced", ["remote", "remote_paged"],
                         indirect=True)
def test_remote_wire_split_times_the_spans(traced):
    """The remote records' ``serialize_s`` / ``channel_s`` /
    ``deserialize_s`` are stamped over the ``kvcomm.wire.*`` spans: per
    phase, the records' sum and the spans' sum agree to the few
    microseconds that bracket each span."""
    events, _, records, _, _ = traced
    assert records
    for name, field in spans.WIRE_FIELDS.items():
        timed = [e for e in events if e[2] == name]
        traced_s = sum(e[1] - e[0] for e in timed) / 1e9
        stamped_s = sum(getattr(r, field) for r in records)
        assert traced_s > 0 and stamped_s > 0, name
        assert traced_s <= stamped_s * 1.01 + 1e-4, name
        assert stamped_s - traced_s <= 0.1 * stamped_s + 50e-6 * len(timed), \
            name
