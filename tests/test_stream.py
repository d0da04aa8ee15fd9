"""Streaming session driver: workload, merge algebra, live telemetry.

Four legs, matching the layer's contract:

* **Workload** — every arrival process expands to a seeded, exactly-
  ``horizon``-message schedule; validation rejects malformed processes;
  the load-sized window is a sane multiple of 64.
* **Merge algebra** — per-chunk ``MetricsBlock`` snapshots differenced
  into deltas re-merge to the end-of-run totals *bit-exactly under any
  association order*, across fusion depths K ∈ {1, 2, 8} (fixtures) and
  for random block contents (hypothesis property). This is the
  invariant that makes live telemetry == post-hoc reporting.
* **Horizon mode** — the engine's ``drain_sink`` path issues exactly
  the dispatches of the equivalent batch run (the telemetry rides the
  drains that already happen), returns no O(M) mirrors, enforces its
  mode contract (no recorder/resume, metrics required, no dense
  fallback), and the session's live sketch equals the device's final
  cumulative histogram.
* **Telemetry** — SLO watchdogs are edge-triggered (one event per
  breach/recovery transition, not per sample), the tracer's counter and
  instant events validate against the Chrome-trace schema, and the
  ``no_drains`` flag surfaces on drain-free span sets.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import FailureScenario, RSMConfig, SimConfig
from repro.core.simulator import (_run_windowed_batch,
                                  chunk_dispatch_count, run_simulation,
                                  spec_with_failures)
from repro.obs.live import (LatencySketch, LiveSample, SLOConfig,
                            SLOWatchdog)
from repro.obs.metrics import (NUM_LATENCY_BUCKETS, MetricsBlock,
                               delta_metrics_block, merge_metrics_blocks,
                               zero_metrics_block)
from repro.obs.report import validate_chrome_trace
from repro.obs.tracer import SpanTracer
from repro.stream import (ArrivalProcess, StreamConfig, StreamSession,
                          arrivals_per_round, build_stream_spec,
                          dispatch_rounds, stream_window_slots)

BFT1 = RSMConfig.bft(1)
KINDS = ("constant", "diurnal", "bursty", "heavytail")


def _sim(k: int = 8, window_slots="auto") -> SimConfig:
    return SimConfig(window=1, phi=6, window_slots=window_slots,
                     chunk_steps=8, superchunk=k)


def _stream_spec(horizon: int = 256, k: int = 8, kind: str = "constant",
                 rate: float = 4.0, failures=None, window_slots="auto"):
    spec = build_stream_spec(BFT1, BFT1, _sim(k, window_slots),
                             ArrivalProcess(kind=kind, rate=rate),
                             horizon)
    if failures is not None:
        spec = spec_with_failures(spec, failures)
    return spec


class _CaptureSink:
    """Horizon-mode sink that keeps every cumulative block snapshot."""

    def __init__(self):
        self.blocks, self.bases, self.ts = [], [], []
        self.final = None

    def on_chunk(self, t_end, metrics, queue, block, bases):
        self.ts.append(int(t_end))
        self.blocks.append(MetricsBlock(
            *(np.asarray(v, dtype=np.int64) for v in block)))
        self.bases.append(np.asarray(bases))

    def on_final(self, state, mc, bases, w, growth_events, t):
        self.final = dict(mc=mc, bases=np.asarray(bases), w=int(w),
                          growth=growth_events, t=int(t))


# ---------------------------------------------------------------- workload

@pytest.mark.parametrize("kind", KINDS)
def test_workload_exact_horizon_and_seeded(kind):
    p = ArrivalProcess(kind=kind, rate=3.5, seed=7)
    counts = arrivals_per_round(p, 777)
    assert counts.sum() == 777
    assert (counts >= 0).all()
    assert np.array_equal(counts, arrivals_per_round(p, 777))
    if kind != "constant":   # stochastic kinds move with the seed
        other = arrivals_per_round(dataclasses.replace(p, seed=8), 777)
        assert not (len(other) == len(counts)
                    and np.array_equal(other, counts))


def test_workload_validation():
    with pytest.raises(ValueError):
        ArrivalProcess(kind="nope")
    with pytest.raises(ValueError):
        ArrivalProcess(rate=0.0)
    with pytest.raises(ValueError):
        ArrivalProcess(kind="heavytail", alpha=1.0)
    with pytest.raises(ValueError):
        arrivals_per_round(ArrivalProcess(), 0)


def test_dispatch_rounds_expand():
    counts = np.array([2, 0, 3, 1])
    rounds = dispatch_rounds(counts)
    assert rounds.tolist() == [0, 0, 2, 2, 2, 3]
    assert len(dispatch_rounds(arrivals_per_round(
        ArrivalProcess(rate=2.5), 100))) == 100


def test_stream_window_slots_shape():
    counts = arrivals_per_round(ArrivalProcess(rate=4.0), 4096)
    w = stream_window_slots(counts, 4, 4, 8, phi=6)
    assert w >= 64 and w % 64 == 0
    # sized for the offered load, far below the horizon
    assert w < 4096


# ----------------------------------------------------- merge associativity

def _fold(deltas, order):
    acc = zero_metrics_block()
    for grp in order:
        part = zero_metrics_block()
        for i in grp:
            part = merge_metrics_blocks(part, deltas[i])
        acc = merge_metrics_blocks(acc, part)
    return acc


def _assert_blocks_equal(a, b, msg=""):
    for name in MetricsBlock._fields:
        assert np.array_equal(np.asarray(getattr(a, name)),
                              np.asarray(getattr(b, name))), \
            f"{msg} field {name} differs"


@pytest.mark.parametrize("k", [1, 2, 8])
def test_merge_fold_any_grouping_equals_final(k):
    """Fixture leg of the associativity contract: per-chunk snapshots
    from a real (lossy, resending) horizon run re-merge to the final
    cumulative block under left, balanced and ragged groupings."""
    # full-width window (W = M): the crash scenario stalls retirement
    # hard enough to outgrow a load-sized window at this tiny horizon
    spec = _stream_spec(horizon=192, k=k, window_slots=192,
                        failures=FailureScenario.crash_fraction(
                            4, 4, 0.25, seed=3, at_step=8))
    sink = _CaptureSink()
    assert _run_windowed_batch([spec], drain_sink=sink) == []
    assert sink.final is not None
    n = len(sink.blocks)
    assert n >= 2, "need several chunks to exercise grouping"
    deltas, prev = [], None
    for blk in sink.blocks:
        deltas.append(delta_metrics_block(prev, blk))
        prev = blk
    final = sink.blocks[-1]
    idx = list(range(n))
    groupings = [
        [[i] for i in idx],                       # left fold
        [idx[: n // 2], idx[n // 2:]],            # one split
        [idx[i:i + 3] for i in range(0, n, 3)],   # ragged triples
    ]
    for g, order in enumerate(groupings):
        _assert_blocks_equal(_fold(deltas, order), final,
                             f"K={k} grouping#{g}")


def test_final_blocks_k_invariant():
    """The cumulative end-of-run block does not depend on fusion depth."""
    finals = []
    for k in (1, 2, 8):
        sink = _CaptureSink()
        _run_windowed_batch([_stream_spec(horizon=192, k=k)],
                            drain_sink=sink)
        finals.append(sink.blocks[-1])
    _assert_blocks_equal(finals[0], finals[1], "K=1 vs K=2")
    _assert_blocks_equal(finals[0], finals[2], "K=1 vs K=8")


try:
    from hypothesis import given, settings, strategies as st

    def _rand_block(draw):
        fields = {}
        for name in MetricsBlock._fields:
            if name == "latency_hist":
                # the fabric's histogram width, so a drawn block merges
                # with zero_metrics_block()
                fields[name] = np.asarray(
                    draw(st.lists(st.integers(0, 1 << 40),
                                  min_size=NUM_LATENCY_BUCKETS,
                                  max_size=NUM_LATENCY_BUCKETS)),
                    dtype=np.int64)
            else:
                fields[name] = np.int64(draw(st.integers(0, 1 << 40)))
        return MetricsBlock(**fields)

    @settings(max_examples=40, deadline=None)
    @given(data=st.data())
    def test_property_merge_associative(data):
        """merge(a, merge(b, c)) == merge(merge(a, b), c) bit-exactly
        for arbitrary block contents (integer algebra: + and max)."""
        a, b, c = (_rand_block(data.draw) for _ in range(3))
        _assert_blocks_equal(
            merge_metrics_blocks(a, merge_metrics_blocks(b, c)),
            merge_metrics_blocks(merge_metrics_blocks(a, b), c))

    @settings(max_examples=40, deadline=None)
    @given(data=st.data(), n=st.integers(2, 6))
    def test_property_delta_fold_roundtrip(data, n):
        """Differencing a nondecreasing cumulative snapshot sequence and
        re-merging the deltas reproduces the last snapshot exactly."""
        cum, seq = None, []
        for _ in range(n):
            inc = _rand_block(data.draw)
            cum = inc if cum is None else merge_metrics_blocks(cum, inc)
            seq.append(cum)
        acc, prev = zero_metrics_block(), None
        for blk in seq:
            acc = merge_metrics_blocks(acc,
                                       delta_metrics_block(prev, blk))
            prev = blk
        _assert_blocks_equal(acc, seq[-1])
except ImportError:  # pragma: no cover - hypothesis is optional
    pass


# ------------------------------------------------------------ horizon mode

def test_session_live_equals_posthoc_and_zero_extra_dispatches():
    session = StreamSession(BFT1, BFT1, _sim(),
                            StreamConfig(horizon=512))
    d0 = chunk_dispatch_count()
    res = session.run()
    stream_d = chunk_dispatch_count() - d0
    assert res.problems == []
    assert res.delivered == 512
    d0 = chunk_dispatch_count()
    batch = run_simulation(session.spec)
    batch_d = chunk_dispatch_count() - d0
    assert stream_d == batch_d
    assert bool((batch.deliver_time >= 0).all())
    assert res.sketch.total() == 512
    assert res.retired == 512
    assert res.capacity["sustained_frac"] > 0


def _nest(spans):
    """(outer, inner) pairs; raises unless every two spans either nest
    or are disjoint (the order profiler annotations need)."""
    pairs = []
    for a in spans:
        for b in spans:
            if a is b:
                continue
            a0, a1 = a.start_ns, a.start_ns + a.dur_ns
            b0, b1 = b.start_ns, b.start_ns + b.dur_ns
            if a0 <= b0 and b1 <= a1 and (a0, -a1) < (b0, -b1):
                pairs.append((a.name, b.name))
            elif a0 < b0 < a1 < b1:
                raise AssertionError(f"{a.name} and {b.name} overlap")
    return pairs


def test_session_spans_cover_the_host_loop():
    """In a 2-lane session every host-loop span the engine emits sits
    inside ``run``: one ``prologue``, a ``plan`` per dispatch, an
    ``unpack`` per drain, a ``sink_chunk`` per drained chunk; the
    ``dispatch`` spans keep their args; and the spans nest, as the
    profiler's annotations must."""
    from repro.obs.tracer import _annotations

    session = StreamSession(BFT1, BFT1, _sim(),
                            StreamConfig(horizon=512, links=2))
    session.run()                                   # compile first
    tr = SpanTracer()
    res = session.run(tracer=tr)
    c = res.counters
    assert tr.count("run") == tr.count("prologue") == 1
    launches = tr.count("dispatch") + tr.count("compile")
    assert tr.count("plan") == launches == c["dispatches"] > 1
    assert tr.count("unpack") == tr.count("drain_wait") == launches
    assert tr.count("sink_chunk") == c["chunks_drained"] > launches
    assert c["launched_ahead"] == sum(
        s.args["overlapped"] for s in tr.spans if s.name == "drain_wait")
    for s in tr.spans:
        if s.name == "dispatch":
            assert set(s.args) == {"t", "k"}
        if s.name == "plan":
            assert set(s.args) == {"t", "k", "async_ok", "why_sync"}
    host = [s for s in tr.spans if s.cat != "slo"]
    pairs = set(_nest(host))
    for name in ("prologue", "plan", "dispatch", "drain_wait", "unpack",
                 "sink_chunk", "final_flush"):
        assert ("run", name) in pairs, name
    assert ("unpack", "sink_chunk") in pairs
    assert _annotations() == []         # every annotation closed
    # the spans under ``run`` leave little of it unnamed
    run = next(s for s in tr.spans if s.name == "run")
    named, end = 0, run.start_ns
    for s in sorted((s for s in host if s.name != "run"),
                    key=lambda s: s.start_ns):
        lo, hi = max(s.start_ns, end), s.start_ns + s.dur_ns
        if hi > lo:
            named += hi - lo
            end = hi
    assert named > 0.8 * run.dur_ns


def test_sink_mode_contract_validation():
    spec = _stream_spec(horizon=128)
    with pytest.raises(ValueError, match="recorder"):
        _run_windowed_batch([spec], drain_sink=_CaptureSink(),
                            recorder=object())
    bare = dataclasses.replace(spec, collect_metrics=False)
    with pytest.raises(ValueError, match="collect_metrics"):
        _run_windowed_batch([bare], drain_sink=_CaptureSink())


def test_sink_mode_refuses_dense_fallback():
    """A retirement-stalled stream escalates adaptive growth until the
    next doubling would reach the full horizon (the dense layout);
    horizon mode must raise instead of allocating O(M)."""
    spec = _stream_spec(horizon=192,
                        failures=FailureScenario.crash_fraction(
                            4, 4, 0.25, seed=3, at_step=8))
    with pytest.raises(RuntimeError, match="window overflow"):
        _run_windowed_batch([spec], drain_sink=_CaptureSink())


def test_multi_link_chained_session_delivers():
    cfg = StreamConfig(horizon=256, links=3, chained=True)
    res = StreamSession(BFT1, BFT1, _sim(), cfg).run()
    assert res.problems == []
    assert res.delivered == 256 * 3


# -------------------------------------------------------------- telemetry

def _sample(**kw) -> LiveSample:
    base = dict(t=0, delivered=0, retired=0, backlog=0, gc_lag=0,
                resends=0, losses=0, throughput=0.0, goodput=0.0,
                resend_rate=0.0, p50=0, p95=0, p99=0, p99_recent=0,
                occupancy_hwm=0, rounds_elapsed=0)
    base.update(kw)
    return LiveSample(**base)


def test_slo_watchdog_edge_triggered():
    wd = SLOWatchdog(SLOConfig(p99_latency_rounds=64, resend_rate=None,
                               frontier_stall_chunks=None))
    seq = [10, 100, 120, 90, 10, 10]   # breach at #1, recover at #4
    events = []
    for i, p in enumerate(seq):
        events += wd.check(_sample(t=i, p99_recent=p))
    assert [(e.kind, e.recovered, e.t) for e in events] == [
        ("p99_latency", False, 1), ("p99_latency", True, 4)]
    assert wd.events == events


def test_slo_watchdog_frontier_stall_counts_chunks():
    wd = SLOWatchdog(SLOConfig(p99_latency_rounds=None,
                               resend_rate=None,
                               frontier_stall_chunks=3))
    events = []
    for i in range(6):                  # frozen frontier, live backlog
        events += wd.check(_sample(t=i, retired=5, backlog=9))
    assert len(events) == 1 and not events[0].recovered
    events += wd.check(_sample(t=6, retired=6, backlog=9))
    assert len(events) == 2 and events[-1].recovered


def test_tracer_no_drains_flag_and_counters():
    tr = SpanTracer()
    with tr.span("run", cat="engine"):
        tr.counter("stream/rate", throughput=3.5, goodput=3.0)
        tr.instant("slo:p99_latency", cat="slo", recovered=False)
    assert tr.no_drains()
    d = tr.to_dict()
    assert d["no_drains"] is True
    assert d["counter_samples"] == 1
    assert d["instant_events"] == 1
    assert "no_drains" in tr.summary()
    with tr.span("drain_wait", cat="drain"):
        pass
    assert not tr.no_drains()
    assert tr.to_dict()["no_drains"] is False


def test_chrome_trace_counter_and_instant_schema():
    tr = SpanTracer()
    with tr.span("run", cat="engine"):
        tr.counter("stream/backlog", backlog=12, gc_lag=3)
        tr.instant("slo:resend_rate", cat="slo", value=0.7)
    trace = tr.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    phs = {e["ph"] for e in trace["traceEvents"]}
    assert {"X", "C", "i"} <= phs
    # schema rejects non-numeric counter args and bad instant scopes
    bad_counter = {"name": "c", "cat": "counter", "ph": "C", "ts": 0,
                   "pid": 1, "tid": 1, "args": {"v": "high"}}
    bad_instant = {"name": "i", "cat": "slo", "ph": "i", "ts": 0,
                   "pid": 1, "tid": 1, "s": "x", "args": {}}
    empty_counter = dict(bad_counter, args={})
    for ev in (bad_counter, bad_instant, empty_counter):
        assert validate_chrome_trace({"traceEvents": [ev]}), ev


def test_latency_sketch_merge_and_percentiles():
    a = LatencySketch.empty()
    h = np.zeros_like(np.asarray(a.hist))
    h[0], h[3] = 90, 10
    b = LatencySketch(hist=h)
    merged = a.merge(b).merge(b)
    assert merged.total() == 200
    assert merged.percentile(50) <= merged.percentile(99)


def test_floor_planner_streaming_keeps_no_history():
    from repro.topology.engine import FloorPlanner
    fp = FloorPlanner.chain(3, 1000, keep_history=False)
    floors = fp(8, np.array([7, 5, 2]))
    assert floors.tolist() == [1000, 7, 5]
    fp(16, np.array([9, 8, 5]))
    assert fp.history == [] and fp.calls == 2
    assert fp.last.tolist() == [1000, 9, 8]


@pytest.mark.slow
def test_stream_acceptance_long_horizon():
    """Horizon ≫ any batch allocation: W stays load-sized, every message
    delivers, and the live fold equals the device totals bit-exactly."""
    cfg = StreamConfig(horizon=65536,
                       process=ArrivalProcess(rate=16.0))
    res = StreamSession(BFT1, BFT1, _sim(), cfg).run()
    assert res.problems == []
    assert res.delivered == 65536
    assert res.final_window_slots <= 2048          # W << M
    assert res.counters["live_rows"] <= 256 + res.live.total_rows
    assert len(res.live.rows) <= res.live.rows.maxlen
