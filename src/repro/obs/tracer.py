"""Host-side span tracer for the windowed engine's control loop.

Monotonic-clock wall-time spans with *explicit* begin/end — the traced
chunk programs never see a clock; all timestamps are taken in the host
loop between dispatches, so jaxprs are unaffected (the PR 6 auditor
stays green by construction).

A :class:`SpanTracer` is installed for the dynamic extent of a run with
:func:`tracing`; the engine's instrumentation points go through
:func:`obs_begin` / :func:`obs_end`, which are no-ops (and take no
clock samples) when no tracer is installed. While one is installed,
each span begun with a name is also a ``jax.profiler.TraceAnnotation``
of that name, so a profiled run shows the program's spans on the
profiler's own clock, beside the device's operations. Those
annotations must close in LIFO order on their thread: :func:`obs_end`
first closes any annotation begun inside the span that was left open
(an exception skipped its ``obs_end``).

Canonical span names emitted by the engine
(``tests/test_obs.py`` and ``tests/test_stream.py`` assert these):

  ``run``             whole ``_run_windowed_batch`` invocation; its
                      children below cover all of it
  ``prologue``        entry to the first loop iteration: spec checks,
                      host mirrors, stacked failure masks, initial carry
  ``plan``            loop top up to the dispatch: failure-schedule
                      swap, floors, overflow check, the choice of ``k``;
                      args ``t``, ``k``, ``async_ok`` and ``why_sync``
                      (why the dispatch is drained before the next one
                      launches: ``k1``, ``bound``, ``last``,
                      ``host_boundary``; None when it may launch ahead)
  ``compile``         a dispatch that traced at least one new program
  ``dispatch``        enqueue of an already-compiled chunk/superchunk
                      (the profiler names both ``dispatch``; a compile
                      carries ``span=compile`` there)
  ``drain_wait``      blocking ``device_get`` of a dispatch's queue;
                      ``args.overlapped`` is True when the fetched
                      dispatch had a successor already in flight
                      (PR 5 double buffering doing its job)
  ``unpack``          the rest of a drain: per-chunk split of the
                      fetched queue and metrics, base bookkeeping
  ``sink_chunk``      one ``drain_sink.on_chunk`` call (inside
                      ``unpack``; horizon mode only)
  ``plan_floors``     topology commit-floor planning callback
  ``checkpoint``      recorder snapshot capture
  ``window_growth``   adaptive 2x window growth (state re-pad)
  ``dense_migration`` windowed -> dense layout fallback
  ``final_flush``     terminal state fetch + retire scatter

Export: :meth:`SpanTracer.export_chrome_trace` writes Chrome
trace-event JSON loadable in Perfetto / ``chrome://tracing``;
:meth:`SpanTracer.summary` renders a flamegraph-style text table.
The PR 5 async double buffering becomes a first-class number via
:meth:`SpanTracer.drain_overlap_ratio`.
"""

from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Span",
    "CounterSample",
    "InstantEvent",
    "SpanTracer",
    "tracing",
    "current_tracer",
    "OpenSpan",
    "obs_begin",
    "obs_end",
    "obs_span",
]


@dataclass
class Span:
    """One closed wall-time interval."""

    name: str
    start_ns: int
    dur_ns: int
    cat: str = "host"
    args: Dict[str, Any] = field(default_factory=dict)


@dataclass
class CounterSample:
    """One sample on a named Perfetto counter track (``ph: "C"``)."""

    name: str
    ts_ns: int
    values: Dict[str, float] = field(default_factory=dict)


@dataclass
class InstantEvent:
    """One point-in-time marker (``ph: "i"``) — e.g. an SLO breach."""

    name: str
    ts_ns: int
    cat: str = "host"
    args: Dict[str, Any] = field(default_factory=dict)


class SpanTracer:
    """Collects :class:`Span` records against one monotonic origin.

    Besides duration spans it carries two live-telemetry event kinds:
    counter samples (numeric track values — throughput, backlog, p99 —
    rendered as Perfetto counter tracks) and instant events (SLO
    watchdog breaches / recoveries on the same timeline).
    """

    def __init__(self, pid: int = 0, tid: int = 0):
        self.pid = pid
        self.tid = tid
        self.origin_ns = time.monotonic_ns()
        self.spans: List[Span] = []
        self.counters: List[CounterSample] = []
        self.instants: List[InstantEvent] = []

    # -- recording ---------------------------------------------------

    def begin(self) -> int:
        return time.monotonic_ns()

    def counter(self, name: str, **values: float) -> CounterSample:
        cs = CounterSample(name=name, ts_ns=time.monotonic_ns(),
                           values={k: float(v) for k, v in values.items()})
        self.counters.append(cs)
        return cs

    def instant(self, name: str, cat: str = "host",
                **args: Any) -> InstantEvent:
        ev = InstantEvent(name=name, ts_ns=time.monotonic_ns(),
                          cat=cat, args=dict(args))
        self.instants.append(ev)
        return ev

    def end(self, begin_ns: int, name: str, cat: str = "host",
            **args: Any) -> Span:
        sp = Span(name=name, start_ns=begin_ns,
                  dur_ns=time.monotonic_ns() - begin_ns,
                  cat=cat, args=dict(args))
        self.spans.append(sp)
        return sp

    @contextmanager
    def span(self, name: str, cat: str = "host", **args: Any):
        b = self.begin()
        try:
            yield
        finally:
            self.end(b, name, cat, **args)

    # -- queries -----------------------------------------------------

    def names(self) -> List[str]:
        return [s.name for s in self.spans]

    def count(self, name: str) -> int:
        return sum(1 for s in self.spans if s.name == name)

    def total_ns(self, name: str) -> int:
        return sum(s.dur_ns for s in self.spans if s.name == name)

    def wall_ns(self) -> int:
        if not self.spans:
            return 0
        end = max(s.start_ns + s.dur_ns for s in self.spans)
        start = min(s.start_ns for s in self.spans)
        return end - start

    def no_drains(self) -> bool:
        """True when the run recorded zero ``drain_wait`` spans — the
        0.0 returned by :meth:`drain_overlap_ratio` then means "nothing
        to overlap", not "overlap failed" (dense path, empty runs)."""
        return not any(s.name == "drain_wait" for s in self.spans)

    def drain_overlap_ratio(self) -> float:
        """Fraction of drain-wait time spent with a successor dispatch
        already in flight (1.0 = every drain overlapped compute).

        Defined as 0.0 when there were no drain spans at all; check
        :meth:`no_drains` (exported as the ``no_drains`` field in
        :meth:`to_dict` / ``RunReport``) to tell the cases apart."""
        tot = over = 0
        for s in self.spans:
            if s.name != "drain_wait":
                continue
            tot += s.dur_ns
            if s.args.get("overlapped"):
                over += s.dur_ns
        return over / tot if tot else 0.0

    # -- export ------------------------------------------------------

    def to_chrome_trace(self) -> dict:
        events = []
        for s in self.spans:
            events.append({
                "name": s.name,
                "cat": s.cat,
                "ph": "X",
                "ts": (s.start_ns - self.origin_ns) / 1000.0,
                "dur": s.dur_ns / 1000.0,
                "pid": self.pid,
                "tid": self.tid,
                "args": s.args,
            })
        for c in self.counters:
            events.append({
                "name": c.name,
                "cat": "counter",
                "ph": "C",
                "ts": (c.ts_ns - self.origin_ns) / 1000.0,
                "pid": self.pid,
                "tid": self.tid,
                "args": c.values,
            })
        for ev in self.instants:
            events.append({
                "name": ev.name,
                "cat": ev.cat,
                "ph": "i",
                "s": "t",   # thread-scoped marker
                "ts": (ev.ts_ns - self.origin_ns) / 1000.0,
                "pid": self.pid,
                "tid": self.tid,
                "args": ev.args,
            })
        events.sort(key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=None)
        return path

    def to_dict(self) -> dict:
        return {
            "origin_ns": self.origin_ns,
            "drain_overlap_ratio": self.drain_overlap_ratio(),
            "no_drains": self.no_drains(),
            "counter_samples": len(self.counters),
            "instant_events": len(self.instants),
            "spans": [{
                "name": s.name, "cat": s.cat,
                "start_ns": s.start_ns - self.origin_ns,
                "dur_ns": s.dur_ns, "args": s.args,
            } for s in self.spans],
        }

    def summary(self) -> str:
        """Flamegraph-style text rollup, widest spans first."""
        agg: Dict[str, List[int]] = {}
        for s in self.spans:
            ent = agg.setdefault(s.name, [0, 0])
            ent[0] += 1
            ent[1] += s.dur_ns
        wall = max(self.wall_ns(), 1)
        lines = ["%-16s %6s %12s %10s %7s"
                 % ("span", "count", "total_ms", "avg_ms", "%wall")]
        for name, (n, tot) in sorted(agg.items(),
                                     key=lambda kv: -kv[1][1]):
            lines.append("%-16s %6d %12.3f %10.3f %6.1f%%"
                         % (name, n, tot / 1e6, tot / 1e6 / n,
                            100.0 * tot / wall))
        if self.no_drains():
            lines.append("drain_overlap_ratio n/a (no_drains)")
        else:
            lines.append("drain_overlap_ratio %.3f"
                         % self.drain_overlap_ratio())
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Ambient tracer — engine hooks are no-ops unless one is installed.
# ---------------------------------------------------------------------------

_CURRENT: List[Optional[SpanTracer]] = [None]


def current_tracer() -> Optional[SpanTracer]:
    return _CURRENT[0]


@contextmanager
def tracing(tracer: SpanTracer):
    """Install ``tracer`` as the ambient tracer for this block."""
    prev = _CURRENT[0]
    _CURRENT[0] = tracer
    try:
        yield tracer
    finally:
        _CURRENT[0] = prev


@dataclass
class OpenSpan:
    """A span begun by :func:`obs_begin`, handed back to :func:`obs_end`."""

    name: Optional[str]
    begin_ns: int
    annotation: Optional[TraceAnnotation] = None


_OPEN = threading.local()      # per thread: annotations entered, in order


def _annotations() -> List[TraceAnnotation]:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


def _close(annotation: TraceAnnotation) -> None:
    """Exit ``annotation`` and, first, every one entered after it that is
    still open, so the profiler's annotations always nest; one already
    closed that way is left alone."""
    stack = _annotations()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i] is annotation:
            for inner in reversed(stack[i:]):
                inner.__exit__(None, None, None)
            del stack[i:]
            return


def obs_begin(name: Optional[str] = None) -> Optional[OpenSpan]:
    """Begin a prospective span; None (no clock sample) when tracing is
    disabled. With ``name`` the span is a profiler annotation as well."""
    tr = _CURRENT[0]
    if tr is None:
        return None
    annotation = None
    if name is not None:
        annotation = TraceAnnotation(name)
        annotation.__enter__()
        _annotations().append(annotation)
    return OpenSpan(name, tr.begin(), annotation)


def obs_end(span: Optional[OpenSpan], name: str, cat: str = "host",
            **args: Any) -> None:
    if span is None:
        return
    if span.annotation is not None:
        if name != span.name:      # named at its end (compile/dispatch)
            span.annotation.set_metadata(span=name)
        _close(span.annotation)
    tr = _CURRENT[0]
    if tr is not None:
        tr.end(span.begin_ns, name, cat, **args)


@contextmanager
def obs_span(name: str, cat: str = "host", **args: Any):
    b = obs_begin(name)
    try:
        yield
    finally:
        obs_end(b, name, cat, **args)
