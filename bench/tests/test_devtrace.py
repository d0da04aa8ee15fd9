"""The reduction from a device trace to busy time, idle gaps and top
operations, on hand-made events and on a slice of a trace recorded on a
TPU v5e (``bench/testdata/trace_slice.json``)."""

from pathlib import Path

import numpy as np
import pytest

from bench import devtrace
from bench.devtrace import Event

DEV = "/device:TPU:0"
SLICE = Path(__file__).resolve().parents[1] / "testdata" / "trace_slice.json"


def _ops(*spans):
    return [Event(DEV, "XLA Ops", name, s, d) for name, s, d in spans]


def test_busy_is_the_union_clipped_to_the_session():
    events = [Event("/host:CPU", "python", devtrace.SESSION, 100, 1000)]
    events += _ops(("fusion.1", 50, 100),      # 50 ns inside
                   ("fusion.2", 300, 200),     # overlaps the next
                   ("fusion.1", 400, 200),
                   ("copy", 1050, 100))        # 50 ns inside
    events.append(Event(DEV, "XLA Modules", "jit_superchunk", 0, 2000))
    host = [("run", 90, 1200), ("dispatch", 150, 290),
            ("drain_wait", 600, 1000)]
    r = devtrace.reduce_events(events, host)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx((50 + 300 + 50) * 1e-9)
    assert r.idle_share == pytest.approx(0.6)
    assert dict(r.device_ops) == pytest.approx(
        {"fusion.1": 250e-9, "fusion.2": 200e-9, "copy": 50e-9})
    # gaps: [150, 300) in dispatch, [600, 1050) in drain_wait
    assert dict(r.idle_gaps) == pytest.approx(
        {"dispatch": 150e-9, "drain_wait": 450e-9})


def test_a_loop_keeps_only_the_time_its_body_does_not_cover():
    events = [Event("/host:CPU", "python", devtrace.SESSION, 0, 1000)]
    events += _ops(("%while.3 = (s32[]) while(..)", 100, 800),
                   ("%fusion.7 = pred[8] fusion(..)", 150, 300),
                   ("%fusion.8 = s32[8] fusion(..)", 500, 350))
    r = devtrace.reduce_events(events)
    assert r.busy_s == pytest.approx(800e-9)
    assert r.device_ops == [["fusion.8", pytest.approx(350e-9)],
                            ["fusion.7", pytest.approx(300e-9)],
                            ["while.3", pytest.approx(150e-9)]]


def test_nothing_to_read_gives_nothing():
    assert devtrace.reduce_events(_ops(("fusion", 0, 10))) is None
    session = [Event("/host:CPU", "python", devtrace.SESSION, 0, 10)]
    assert devtrace.reduce_events(session) is None


@pytest.mark.skipif(not SLICE.exists(), reason="no recorded trace slice")
def test_recorded_slice_against_a_plain_count():
    events = devtrace.load_events(str(SLICE))
    r = devtrace.reduce_events(events)
    mark = [e for e in events if e.name == devtrace.SESSION][0]
    lo, hi = mark.start_ns, mark.start_ns + mark.dur_ns
    ops = devtrace.device_op_events(events)
    assert ops
    # a plain count on a 100 ns grid of what the union must hold
    grid = np.zeros(int((hi - lo) // 100) + 1, bool)
    for e in ops:
        a = max(e.start_ns, lo)
        b = min(e.start_ns + e.dur_ns, hi)
        if b > a:
            grid[int((a - lo) // 100):int(np.ceil((b - lo) / 100))] = True
    assert r.busy_s == pytest.approx(grid.sum() * 100e-9, rel=0.02)
    assert 0 < r.busy_s <= r.window_s
