"""The measured window: whole warm sessions of the served path, back to
back, each recorded as it drains.

``StreamSession.run`` feeds every drained chunk to its engine sink. For
the length of a run the harness puts a subclass of that sink in its
place (:func:`tapped`) which, before the session's own handling, stamps
the arrival time and keeps a reference to what the chunk carries. Once
the session has ended, outside its timing, the recorder reads from those
the chunk's end round, every lane's retired prefix, every lane's
per-round counts, and one sampled lane's per-message outcomes. That is
what the check compares with the reference; the session's own telemetry
comes from its ``StreamResult``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List, Optional

import numpy as np

from bench.record import (LIVE_FIELDS, OBS_FIELDS, ROUND_FIELDS,
                          SessionRecord)


class Recorder:
    """Collects one session's drained chunks for lane ``lane``."""

    def __init__(self, m: int, n_s: int, n_r: int, lane: int):
        self.lane, self.m, self.n_s, self.n_r = lane, m, n_s, n_r
        self.arrived_ns: List[int] = []
        self.sink_spans: List[tuple] = []
        self.chunks: List[tuple] = []     # (t_end, metrics, queue, bases)
        self.final: Optional[tuple] = None

    def on_chunk(self, now_ns, t_end, metrics, queue, bases) -> None:
        # references only (the engine fetched fresh host arrays and
        # passes a copy of ``bases``): nothing is copied while timed
        self.arrived_ns.append(now_ns)
        self.chunks.append((t_end, metrics, queue, bases))

    def on_final(self, state, bases) -> None:
        """The messages still in the window when the session ended."""
        self.final = (state, bases)

    def record(self, result) -> SessionRecord:
        """This session as a :class:`SessionRecord`, read from the kept
        chunks, with the session's own per-lane totals and last live row
        from ``result``."""
        b, m = self.lane, self.m
        messages = {
            "quack_time": np.full((self.n_s, m), -1, np.int32),
            "deliver_time": np.full(m, -1, np.int32),
            "retry": np.zeros((self.n_s, m), np.int32),
            "recv_has": np.zeros((self.n_r, m), bool)}

        def keep(base, count, src):
            """Window columns ``[0, count)`` of lane ``b`` in ``src``
            hold messages from ``base``."""
            count = min(int(count), m - int(base))
            if count > 0:
                for f, into in messages.items():
                    into[..., base:base + count] = \
                        np.asarray(getattr(src, f))[b][..., :count]

        for _, _, queue, _ in self.chunks:
            keep(np.asarray(queue.base)[b], np.asarray(queue.count)[b],
                 queue)
        if self.final is not None:
            state, bases = self.final
            base = int(np.asarray(bases)[b])
            keep(base, m - base, state)
        rounds = {f: [np.asarray(getattr(c[1], f)) for c in self.chunks]
                  for f in ROUND_FIELDS}
        obs = [{f: (np.asarray(getattr(o, f), dtype=np.int64)
                    if f == "latency_hist" else int(getattr(o, f)))
                for f in OBS_FIELDS} for o in result.obs]
        last = result.live.rows[-1] if result.live.rows else {}
        return SessionRecord(
            chunk_ends=np.asarray([int(c[0]) for c in self.chunks],
                                  dtype=np.int64),
            bases=np.stack([np.asarray(c[3], dtype=np.int64)
                            for c in self.chunks]) if self.chunks else
            np.zeros((0, 0), np.int64),
            rounds={f: (np.concatenate(v, axis=-1) if v else
                        np.zeros((0, 0), np.int64))
                    for f, v in rounds.items()},
            lane=b, messages=messages, obs=obs,
            live={f: last.get(f) for f in LIVE_FIELDS},
            delivered=int(result.delivered), retired=int(result.retired))


class _Slot:
    """Which recorder the tapped sink reports to (one per session)."""

    recorder: Optional[Recorder] = None


@contextlib.contextmanager
def tapped():
    """Put the recording sink in the session module's place for the
    extent of the block; yields the slot to point at a recorder."""
    import repro.stream.session as session_mod

    base_cls = session_mod._EngineSink
    slot = _Slot()

    class RecordingSink(base_cls):
        def on_chunk(self, t_end, metrics, queue, block, bases) -> None:
            now = time.monotonic_ns()
            rec = slot.recorder
            if rec is not None:
                rec.on_chunk(now, t_end, metrics, queue, bases)
            super().on_chunk(t_end, metrics, queue, block, bases)
            if rec is not None:
                rec.sink_spans.append((now, time.monotonic_ns()))

        def on_final(self, state, mc, bases, w, growth_events, t) -> None:
            super().on_final(state, mc, bases, w, growth_events, t)
            if slot.recorder is not None:
                slot.recorder.on_final(state, bases)

    session_mod._EngineSink = RecordingSink
    try:
        yield slot
    finally:
        session_mod._EngineSink = base_cls


@dataclasses.dataclass
class SessionRun:
    """One session of the window: its timing, its result, its record."""

    start_ns: int
    end_ns: int
    lane: int
    record: Optional[SessionRecord]
    spans: List = dataclasses.field(default_factory=list)
    arrived_ns: List[int] = dataclasses.field(default_factory=list)
    sink_spans: List[tuple] = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    problems: List[str] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    traced: bool = False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def run_one(session, slot: _Slot, lane: int,
            traced: bool = False) -> SessionRun:
    """Run ``session`` once, recorded; never raises for the session's
    own failure (a session that fails is counted, not fatal)."""
    from repro.obs.tracer import SpanTracer

    spec = session.spec
    rec = Recorder(spec.m, spec.n_s, spec.n_r, lane)
    slot.recorder = rec
    tracer = SpanTracer()
    start, end = time.monotonic_ns(), None
    try:
        result = session.run(tracer=tracer)
        end = time.monotonic_ns()
        # a session whose output does not read as the cell's (lanes
        # missing, say) failed like one that raised
        record = rec.record(result)
    except Exception as e:          # a broken session is a failed one
        return SessionRun(start, end or time.monotonic_ns(), lane, None,
                          error=repr(e), spans=tracer.spans,
                          arrived_ns=rec.arrived_ns,
                          sink_spans=rec.sink_spans,
                          problems=[f"session failed: {e!r}"],
                          traced=traced)
    finally:
        slot.recorder = None
    return SessionRun(start, end, lane, record,
                      spans=tracer.spans, arrived_ns=rec.arrived_ns,
                      sink_spans=rec.sink_spans,
                      counters=dict(result.counters, rounds=result.rounds),
                      problems=list(result.problems), traced=traced)
