"""The reduction of a device trace to device time by protocol-step scope
and idle time by program span, on hand-made events and on a slice of a
trace recorded on a TPU v5e that keeps each event's stats
(``bench/testdata/scope_slice.json``)."""

from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

from bench import devtrace, scopes
from bench.scopes import StatEvent

DEV = "/device:TPU:0"
HOST = ("/host:CPU", "main")
SLICE = Path(__file__).resolve().parents[1] / "testdata" / "scope_slice.json"


def _op(name, start, dur, op_name=""):
    stats = (("tf_op", op_name),) if op_name else ()
    return StatEvent(DEV, "XLA Ops", name, start, dur, stats)


def _span(name, start, dur, line=HOST[1]):
    return StatEvent(HOST[0], line, name, start, dur)


def test_scope_is_the_innermost_c3b_name():
    assert scopes.scope_of(
        (("tf_op", "jit(superchunk)/while/body/vmap(c3b_quorum)/reduce"),)
    ) == "c3b_quorum"
    assert scopes.scope_of(
        (("tf_op", "jit(f)/c3b_send/c3b_retransmit/gather"),)
    ) == "c3b_retransmit"
    assert scopes.scope_of((("tf_op", "jit(f)/while/body/add"),)) == ""
    assert scopes.scope_of(()) == ""
    # the program's HLO, where read, decides; where it names nothing
    # the metadata the profiler shows still may
    assert scopes.scope_of((("tf_op", "jit(f)/while:"),
                            ("hlo_scope", "c3b_ack"))) == "c3b_ack"
    assert scopes.scope_of((("tf_op", "jit(f)/c3b_rotate/gather"),
                            ("hlo_scope", ""))) == "c3b_rotate"


def _varint(n):
    out = bytearray()
    while True:
        low, n = n & 0x7F, n >> 7
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _pb(field, value):
    """One protobuf field: an int as a varint, else length-delimited."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _instr(iid, name, opcode, op_name="", operands=(), called=()):
    msg = _pb(1, name) + _pb(2, opcode) + _pb(35, iid)
    if op_name:
        msg += _pb(7, _pb(2, op_name))
    if operands:     # packed, as XLA writes it
        msg += _pb(36, b"".join(_varint(o) for o in operands))
    for c in called:     # unpacked also decodes
        msg += _pb(38, c)
    return _pb(2, msg)


def _hlo():
    fused = _pb(5, 1) + _instr(1, "a", "add", "jit(f)/c3b_telemetry/add") \
        + _instr(2, "b", "multiply", "jit(f)/c3b_telemetry/mul") \
        + _instr(3, "c", "scatter")
    entry = _pb(5, 2) + _instr(10, "x", "parameter") \
        + _instr(11, "rw", "reduce-window", operands=[10]) \
        + _instr(12, "sl", "slice", operands=[11]) \
        + _instr(13, "use", "fusion", "jit(f)/vmap(c3b_ack)/reduce_sum",
                 operands=[12]) \
        + _instr(14, "fus", "fusion", called=[1]) \
        + _instr(15, "g", "gather", "jit(f)/c3b_send/c3b_retransmit/gather")
    return _pb(1, _pb(3, fused) + _pb(3, entry))


def test_hlo_scopes_fill_what_xla_left_unnamed():
    """An instruction keeps its own scope; a fusion without one takes
    its fused instructions'; anything else without one (a rewrite's
    ``reduce-window``) takes its nearest named consumer's."""
    got = scopes.hlo_scopes(_hlo())
    assert got["g"] == "c3b_retransmit"
    assert got["use"] == "c3b_ack"
    assert got["fus"] == "c3b_telemetry"
    assert got["rw"] == got["sl"] == got["x"] == "c3b_ack"


def test_op_metadata_from_a_serialized_xspace():
    def entry(key, msg):
        return _pb(1, key) + _pb(2, msg)

    def stat(mid, field, value):
        return _pb(5, _pb(1, mid) + _pb(field, value))

    device = _pb(2, "/device:TPU:0") \
        + _pb(3, _pb(2, "XLA Ops")) \
        + _pb(4, entry(5, _pb(1, 5) + _pb(2, "%g = gather(..)")
                       + _pb(4, "g") + stat(1, 3, 77)
                       + stat(2, 5, "jit(f)/c3b_retransmit/gather")
                       + stat(3, 7, 4))) \
        + b"".join(_pb(5, entry(i, _pb(1, i) + _pb(2, n))) for i, n in
                   ((1, "program_id"), (2, "tf_op"), (3, "hlo_category"),
                    (4, "gather")))
    meta = _pb(2, "/host:metadata") \
        + _pb(4, entry(1, _pb(2, "jit_f(77)") + stat(9, 6, _hlo()))) \
        + _pb(5, entry(9, _pb(1, 9) + _pb(2, "Hlo Proto")))
    ops, programs = scopes.op_metadata(_pb(1, device) + _pb(1, meta))
    assert ops == {(77, "%g = gather(..)"): {
        "display_name": "g", "program_id": 77,
        "tf_op": "jit(f)/c3b_retransmit/gather", "hlo_category": "gather"}}
    assert programs[77]["rw"] == "c3b_ack"


def test_device_time_by_scope_and_idle_by_innermost_span():
    events = [_span(devtrace.SESSION, 0, 1000),
              _span("run", 10, 980),
              _span("prologue", 10, 90),
              _span("plan", 300, 100),
              _span("drain_wait", 320, 30),
              _span("unpack", 600, 200),
              _span("sink_chunk", 650, 100),
              # another thread's event of a span's name is not the
              # program's
              _span("plan", 0, 1000, line="other")]
    events += [_op("%while.1 = (s32[]) while(..)", 100, 200,
                   "jit(s)/while"),
               _op("fusion.1", 120, 100,
                   "jit(s)/vmap(c3b_retransmit)/gather"),
               _op("fusion.2", 220, 50, "jit(s)/c3b_quorum/reduce"),
               _op("copy.3", 400, 100)]
    r = scopes.reduce_events(events)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(300e-9)
    assert r.scope_s == pytest.approx({"c3b_retransmit": 100e-9,
                                       "c3b_quorum": 50e-9,
                                       "": 150e-9})
    # the loop's own 50 ns and the unscoped copy
    assert r.unscoped_share == pytest.approx(150 / 300)
    # idle: [0, 100) = run's start at 10, prologue [10, 100);
    # [300, 400) = plan, drain_wait [320, 350); [500, 1000) = run,
    # unpack [600, 800) with sink_chunk [650, 750), run to 990, none
    assert r.idle_s == pytest.approx({
        "": 20e-9, "prologue": 90e-9, "plan": 70e-9, "drain_wait": 30e-9,
        "run": 100e-9 + 190e-9, "unpack": 100e-9, "sink_chunk": 100e-9})
    assert r.idle_unexplained_s == pytest.approx(310e-9)
    assert sum(r.idle_s.values()) == pytest.approx(r.window_s - r.busy_s)


def test_a_program_without_scopes_or_spans_gives_nothing_to_read():
    events = [_span(devtrace.SESSION, 0, 100), _op("fusion.1", 10, 20)]
    r = scopes.reduce_events(events)
    assert not r.scoped and not r.spanned
    assert r.idle_s == pytest.approx({"": 80e-9})
    assert scopes.reduce_events([_op("fusion.1", 0, 10)]) is None


@pytest.mark.skipif(not SLICE.exists(), reason="no recorded trace slice")
def test_recorded_slice_against_a_plain_count():
    events = scopes.load_events(str(SLICE))
    r = scopes.reduce_events(events)
    mark = [e for e in events if e.name == devtrace.SESSION][0]
    lo, hi = mark.start_ns, mark.start_ns + mark.dur_ns
    ops = devtrace.device_op_events(events)
    assert r.scoped and r.spanned
    # device self time lands in the step's scopes, and adds up to the
    # busy time the plain reduction finds
    assert r.scope_s.get("c3b_retransmit", 0) > 0
    assert sum(r.scope_s.values()) == pytest.approx(
        devtrace.reduce_events(events).busy_s, rel=0.02)
    # idle time by span, against a plain count on a 100 ns grid: a cell
    # is idle where no op covers it, and named by the latest-begun
    # program span on the session's thread covering it
    n = int((hi - lo) // 100)
    busy = np.zeros(n, bool)
    for e in ops:
        a, b = max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi)
        if b > a:
            busy[int((a - lo) // 100):int(np.ceil((b - lo) / 100))] = True
    spans = sorted((e for e in events if e.plane == mark.plane
                    and e.line == mark.line
                    and e.name in scopes.PROGRAM_SPANS),
                   key=lambda e: (e.start_ns, -e.dur_ns))
    want = defaultdict(float)
    for i in np.nonzero(~busy)[0]:
        x = lo + i * 100 + 50
        name = ""
        for e in spans:
            if e.start_ns <= x < e.start_ns + e.dur_ns:
                name = e.name
        want[name] += 100e-9
    for name, v in want.items():
        assert r.idle_s.get(name, 0.0) == pytest.approx(
            v, abs=0.02 * sum(want.values())), name
