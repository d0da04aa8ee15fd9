"""A device trace of one window session, reduced to busy time, idle gaps
and the operations that took the time.

The JAX profiler records the session in ``.bench_out/trace/``; the
harness wraps the session in a ``TraceAnnotation`` named
:data:`SESSION` and notes the host clock as it enters it, which puts the
program's host spans (``SpanTracer``, host monotonic clock) on the
trace's clock. :func:`reduce_events` works on plain event tuples, so the
tests check it on a small recorded trace (``bench/testdata``).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

SESSION = "bench_session"
# innermost host activity wins when several spans cover an idle gap
HOST_SPANS = ("sink", "drain_wait", "plan_floors", "dispatch", "compile",
              "final_flush", "run")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Reduction:
    window_s: float                 # the traced session, trace clock
    busy_s: float                   # union of device op intervals in it
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def start(trace_dir: str) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def read_events(trace_dir: str) -> List[Event]:
    """Every event of the trace written under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    return [Event(p.name, ln.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for p in data.planes for ln in p.lines for ev in ln.events]


def load_events(path: str) -> List[Event]:
    """Events kept as JSON rows of the :class:`Event` fields, as
    ``bench/testdata/trace_slice.json`` holds a slice of a chip trace."""
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def device_op_events(events: Sequence[Event]) -> List[Event]:
    """The operations run on the first accelerator: its ``XLA Ops``
    line (one event per executed HLO operation)."""
    devices = sorted({e.plane for e in events
                      if e.plane.startswith("/device:")
                      and "CPU" not in e.plane})
    if not devices:
        return []
    return [e for e in events
            if e.plane == devices[0] and e.line == "XLA Ops"]


def op_name(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.17 = pred[..]
    fusion(..)`` is ``fusion.17``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_times(intervals: List[Tuple[float, float]]) -> List[float]:
    """Each interval's length less the intervals nested inside it: a
    while loop or a conditional keeps only the time its body ops do not
    cover, so the top operations are the ones doing the work."""
    own = [max(t - s, 0.0) for s, t in intervals]
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    open_: List[int] = []
    for i in order:
        s, t = intervals[i]
        while open_ and intervals[open_[-1]][1] <= s:
            open_.pop()
        if open_ and t <= intervals[open_[-1]][1]:
            own[open_[-1]] -= t - s
        open_.append(i)
    return own


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _activity(mid: float, host: Sequence[Tuple[str, float, float]]) -> str:
    covering = [name for name, s, e in host if s <= mid < e]
    for name in HOST_SPANS:
        if name in covering:
            return name
    return "outside_session_spans"


def reduce_events(events: Sequence[Event],
                  host_spans: Sequence[Tuple[str, float, float]] = (),
                  clock_offset_ns: float = 0.0,
                  top: int = 10) -> Optional[Reduction]:
    """Busy time, top operations and idle gaps of the traced session.

    ``host_spans`` are ``(name, start_ns, end_ns)`` on the host
    monotonic clock; ``clock_offset_ns`` maps them onto the trace's
    clock. Returns None where the trace holds no session or no device
    operation.
    """
    marks = [e for e in events if e.name == SESSION]
    ops = device_op_events(events)
    if not marks or not ops:
        return None
    w0 = marks[0].start_ns
    w1 = w0 + marks[0].dur_ns
    clipped = [(max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1))
               for e in ops]
    busy = _union([(s, e) for s, e in clipped if e > s])
    busy_ns = sum(e - s for s, e in busy)
    per_op: Dict[str, float] = defaultdict(float)
    for e, self_ns in zip(ops, _self_times(clipped)):
        if self_ns > 0:
            per_op[op_name(e.name)] += self_ns / 1e9
    host = [(n, s + clock_offset_ns, t + clock_offset_ns)
            for n, s, t in host_spans]
    gaps: Dict[str, float] = defaultdict(float)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for s, t in zip(edges[0::2], edges[1::2]):
        if t > s:
            gaps[_activity((s + t) / 2, host)] += (t - s) / 1e9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa
    return Reduction(window_s=(w1 - w0) / 1e9, busy_s=busy_ns / 1e9,
                     device_ops=[[k, v] for k, v in rank(per_op)],
                     idle_gaps=[[k, v] for k, v in rank(gaps)])
