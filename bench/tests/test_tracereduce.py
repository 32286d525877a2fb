"""The trace reduction on a small trace shaped like a TPU v5e profile:
device lines ``XLA Modules`` / ``XLA Ops`` with the op texts the chip
writes, and harness spans on the host plane."""
from types import SimpleNamespace as NS

import pytest

import tracereduce

KERNEL = ('%closed_call.57 = bf16[4,32,1,128]{3,2,1,0} custom-call(s32[4] '
          '%a, s32[4] %b), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={}}')


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def line(name, events):
    return NS(name=name, events=events)


@pytest.fixture
def trace():
    modules = [ev("jit__sender_prefill_jit(111)", 100, 50),
               ev("jit__ragged_decode_step_jit(222)", 200, 40),
               ev("jit__ragged_decode_step_jit(222)", 300, 40)]
    ops = [ev("%fusion.1 = bf16[8] fusion(%p)", 100, 50),
           ev("%while.28 = (s32[]) while(%t)", 200, 40),
           ev(KERNEL, 210, 10),
           ev("%copy.9 = bf16[10,4096] copy(%w)", 225, 5),
           ev("%while.28 = (s32[]) while(%t)", 300, 40),
           ev(KERNEL, 310, 10)]
    device = NS(name="/device:TPU:0",
                lines=[line("XLA Modules", modules), line("XLA Ops", ops)])
    host = NS(name="/host:CPU", lines=[line("python3", [
        ev("bench.wave", 90, 270),
        ev("bench.share", 95, 100),
        ev("bench.sender_prefill", 98, 60),
        ev("bench.decode_step", 195, 10),
        ev("$scheduler.py:282 run", 90, 270)])])
    return tracereduce.reduce_planes(
        [NS(name="/host:metadata", lines=[]), device, host])


def test_window_and_busy_union(trace):
    assert trace.window_s == pytest.approx(270e-9)
    # ops 100-150, 200-240, 300-340 inside [90, 360]
    assert trace.busy_s == pytest.approx(130e-9)


def test_module_times(trace):
    assert trace.module_seconds("_ragged_decode_step_jit") == \
        (pytest.approx(80e-9), 2)
    assert trace.module_seconds("_sender_prefill_jit") == \
        (pytest.approx(50e-9), 1)
    assert trace.module_seconds("_insert_jit") == (0.0, 0)


def test_kernel_ops_and_self_times(trace):
    ks = trace.kernel_ops("_ragged_decode_step_jit")
    assert [(k.start, k.end) for k in ks] == [(210, 220), (310, 320)]
    whiles = [o for o in trace.devices["/device:TPU:0"]["ops"]
              if o.name == "while.28"]
    assert [w.self_ns for w in whiles] == [25, 30]


def test_idle_inside_spans(trace):
    # share span 95-195: busy 100-150, idle 95-100 and 150-195
    assert trace.idle_within(trace.span_events("bench.share")) == \
        pytest.approx(50e-9)


def test_gap_labels(trace):
    gaps = dict(trace.breakdown()["idle_gaps"])
    # gaps 90-100 and 150-200 have their midpoints (95, 175) inside the
    # share span and outside the sender's; 240-300 and 340-360 lie in the
    # wave only
    assert gaps["bench.share"] == pytest.approx(60e-9)
    assert gaps["bench.wave"] == pytest.approx(80e-9)
    ops = dict(trace.breakdown()["device_ops"])
    assert ops["jit__sender_prefill_jit:fusion.1"] == pytest.approx(50e-9)
    assert ops["jit__ragged_decode_step_jit:closed_call.57"] == \
        pytest.approx(20e-9)
