"""The program's ``kvcomm.*`` spans beside the trace reduction: collected
with their stats, invisible to every existing reader, and read by the four
metrics on the program's spans and counters."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

import programspans
import tracereduce
from metrics import (admit_ms, idle_share, queue_wait_ms, sched_host_stall_ms,
                     sender_prefill_ms, share_stall_ms, wire_decode_stall_ms,
                     wire_encode_stall_ms)

KERNEL = ('%closed_call.57 = bf16[4,32,1,128]{3,2,1,0} custom-call(s32[4] '
          '%a, s32[4] %b), custom_call_target="tpu_custom_call"')


def ev(name, start, dur, **stats):
    e = NS(name=name, start_ns=float(start), duration_ns=float(dur))
    if stats:
        e.stats = list(stats.items())
    return e


def planes(program: bool):
    """A window 0-1000: a share (100-400) whose encode and decode leave the
    chip idle, then two ragged steps with host phases between them."""
    modules = [ev("jit__sender_prefill_jit(1)", 110, 90),
               ev("jit__ragged_decode_step_jit(2)", 500, 100),
               ev("jit__ragged_decode_step_jit(2)", 700, 100)]
    ops = [ev("%fusion.1 = bf16[8] fusion(%p)", 110, 90),
           ev("%fusion.2 = s8[8] fusion(%p)", 210, 20),
           ev("%while.3 = (s32[]) while(%t)", 500, 100),
           ev(KERNEL, 520, 30),
           ev("%while.3 = (s32[]) while(%t)", 700, 100),
           ev(KERNEL, 720, 30)]
    host = [ev("bench.wave", 0, 1000),
            ev("bench.share", 100, 300),
            ev("bench.sender_prefill", 105, 100),
            ev("bench.decode_step", 490, 5),
            ev("bench.decode_step", 690, 5),
            ev("$scheduler.py:282 run", 0, 1000)]
    if program:
        host += [ev("kvcomm.sched.admit", 95, 310, rid=7),
                 ev("kvcomm.share", 100, 300, rid=7),
                 ev("kvcomm.sender.prefill", 105, 100),
                 ev("kvcomm.wire.encode", 205, 60),  # idle 205-210, 230-265
                 ev("kvcomm.wire.decode", 265, 40),      # idle 265-305
                 ev("kvcomm.wire.encode", 305, 50),      # idle 305-355
                 ev("kvcomm.wire.decode", 355, 20),      # idle 355-375
                 ev("kvcomm.sched.step", 490, 10),       # busy from 500
                 ev("kvcomm.sched.read", 610, 40),       # idle 610-650
                 ev("kvcomm.sched.poll", 650, 5),        # idle 650-655
                 ev("kvcomm.sched.retire", 660, 10),     # idle 660-670
                 ev("kvcomm.sched.step", 690, 10)]       # idle 690-700
    return [NS(name="/host:metadata", lines=[]),
            NS(name="/device:TPU:0",
               lines=[NS(name="XLA Modules", events=modules),
                      NS(name="XLA Ops", events=ops)]),
            NS(name="/host:CPU", lines=[NS(name="python3", events=host)])]


def context(trace, queue=None):
    comps = [NS(ttft_s=t, **({} if queue is None else {"queue_s": q}))
             for t, q in zip((1.0, 2.0, 3.0), queue or (0, 0, 0))]
    wave = NS(requests=[1, 2, 3], completions=comps,
              stats={"iterations": 2, "occupancy": 0.5})
    return tracereduce.Context(bench=None, waves=[wave], setup_s=1.0,
                               peak={}, trace=trace)


@pytest.fixture
def traces():
    plain = tracereduce.reduce_planes(planes(False))
    ps = planes(True)
    return plain, programspans.attach(tracereduce.reduce_planes(ps), ps)


def test_program_spans_are_collected_with_their_stats(traces):
    spans = traces[1].program_spans
    assert len(spans) == 12
    assert spans[0] == (95, 405, "kvcomm.sched.admit", {"rid": 7})
    # events without stats, as in these planes, read as empty stats
    assert spans[2] == (105, 205, "kvcomm.sender.prefill", {})
    assert programspans.events(traces[0], "kvcomm.share") is None


def test_existing_readers_see_nothing_new(traces):
    plain, spanned = traces
    assert spanned.spans == plain.spans
    assert spanned.breakdown() == plain.breakdown()
    for reader in (share_stall_ms, admit_ms, sender_prefill_ms, idle_share):
        assert reader.read(context(spanned)) == reader.read(context(plain))
    # idle 100-110, 200-210 and 230-400 inside the share
    assert share_stall_ms.read(context(plain)) == pytest.approx(190e-6)


def test_wire_stalls_split_the_share_stall(traces):
    plain, spanned = traces
    enc = wire_encode_stall_ms.read(context(spanned))
    dec = wire_decode_stall_ms.read(context(spanned))
    assert enc == pytest.approx(90e-6)      # 5 + 35 + 50 ns, one share
    assert dec == pytest.approx(60e-6)      # 40 + 20 ns
    assert enc + dec <= share_stall_ms.read(context(spanned))
    for reader in (wire_encode_stall_ms, wire_decode_stall_ms,
                   sched_host_stall_ms):
        assert reader.read(context(plain)) is None


def test_sched_host_stall_per_step(traces):
    # idle 490-500 and 690-700 in the steps, 610-655 in read and poll,
    # 660-670 in retire: 75 ns over two steps
    assert sched_host_stall_ms.read(context(traces[1])) == \
        pytest.approx(37.5e-6)


def test_queue_wait_is_a_percentile_of_the_counter(traces):
    got = queue_wait_ms.read(context(traces[1], queue=(0.0, 0.5, 1.5)))
    assert got == pytest.approx(np.percentile([0.0, 0.5, 1.5], 90) * 1e3)
    assert queue_wait_ms.read(context(traces[1])) is None


def test_coverage_and_idle_by_span(traces):
    spanned = traces[1]
    # idle in the wave: 0-110, 200-210, 230-500, 600-700, 800-1000 = 690;
    # inside program spans: 95-110, 200-210, 230-405, 490-500, 610-655,
    # 660-670, 690-700 = 275
    assert programspans.coverage(spanned) == pytest.approx(275 / 690)
    # relabelled, each gap goes to the innermost program span over its
    # midpoint: 0-110 and 800-1000 to the wave alone, 230-500 (midpoint
    # 365) to the second decode
    gaps = dict(programspans.relabelled(spanned).breakdown()["idle_gaps"])
    assert gaps["bench.wave"] == pytest.approx(310e-9)
    assert gaps["kvcomm.wire.decode"] == pytest.approx(270e-9)
    assert sum(gaps.values()) == pytest.approx(690e-9)
    assert programspans.coverage(traces[0]) is None
