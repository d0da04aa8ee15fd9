"""Observability stack: in-graph metrics fabric, span tracer, reports.

The layer's contract has three legs, and each gets direct coverage:

* **Exactness** — the device-accumulated delivery-latency histogram
  equals the numpy histogram of the per-message ``delivery_latency``
  array for every path (dense, windowed, superchunk-fused, batched
  sweeps, chained topologies, replay resume), and metrics collection
  never perturbs the simulation itself (bit-identical outputs on vs
  off).
* **Zero overhead on the dispatch path** — ``collect_metrics=True``
  adds 0 device dispatches and 0 implicit transfers (the block rides
  the existing drain), at most one extra compile, and with metrics off
  the staged jaxprs are byte-identical to a never-instrumented build.
* **Reporting** — spans carry the canonical engine names, the Chrome
  trace validates against the Perfetto-loadable schema, RunReports
  round-trip through npz+json, and the CLI selftest gate passes.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from helpers import REPO
from repro.core import FailureScenario, RSMConfig, SimConfig
from repro.core.refsim import run_reference
from repro.core.simulator import (build_spec, chunk_dispatch_count,
                                  chunk_trace_count, run_simulation,
                                  run_simulation_batch)
from repro.obs.metrics import (NUM_LATENCY_BUCKETS, bucket_label,
                               latency_histogram_np, percentile_from_hist)
from repro.obs.report import (RunReport, run_reported,
                              run_reported_topology, validate_chrome_trace)
from repro.obs.tracer import SpanTracer, obs_span, tracing

BFT1 = RSMConfig.bft(1)
OUTPUTS = ("quack_time", "deliver_time", "retry", "recv_has")

GC_STALL = FailureScenario(byz_bcast_partial=(True, False, False, False),
                           bcast_limit=2)
STALL_PLUS_CRASH = FailureScenario(
    byz_bcast_partial=(True, False, False, False), bcast_limit=2,
    crash_r=(-1, 8, -1, -1))

# same fusion-break classes as tests/test_pipeline.py: rotation-only,
# adaptive growth, dense-layout fallback, crashed sender
FIXTURES = [
    ("rotating", dict(n_msgs=128, steps=128 // 4 + 40, window=1, phi=6,
                      window_slots=32, chunk_steps=4),
     FailureScenario.none()),
    ("adaptive_growth", dict(n_msgs=128, steps=128 // 4 + 80, window=1,
                             phi=6, window_slots=16, chunk_steps=8),
     GC_STALL),
    ("dense_fallback", dict(n_msgs=64, steps=200, window=1, phi=6,
                            window_slots=16, chunk_steps=8),
     STALL_PLUS_CRASH),
    ("crash_sender", dict(n_msgs=24, steps=150, window=1, phi=6,
                          window_slots=24, chunk_steps=8),
     FailureScenario(crash_s=(1, -1, -1, -1))),
]
IDS = [f[0] for f in FIXTURES]


def _spec(simkw, fails, k, collect=False):
    sim = SimConfig(debug_checks=True, superchunk=k,
                    collect_metrics=collect, **simkw)
    return build_spec(BFT1, BFT1, sim, fails)


def _assert_same_run(a, b):
    for out in OUTPUTS:
        assert np.array_equal(getattr(a, out), getattr(b, out)), out
    assert np.array_equal(a.gc_frontiers, b.gc_frontiers)
    assert np.array_equal(a.send_step, b.send_step)
    assert np.array_equal(a.delivery_latency, b.delivery_latency)
    assert a.window_growth_events == b.window_growth_events


# --- exactness: device metrics vs numpy oracles --------------------------

@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("name,simkw,fails", FIXTURES, ids=IDS)
def test_metrics_exact_and_nonperturbing(name, simkw, fails, k):
    """Metrics-on ≡ metrics-off bit-for-bit, and the device histogram
    equals the numpy histogram of the per-message latency array, across
    every fusion-break class at K ∈ {1, 8}."""
    off = run_simulation(_spec(simkw, fails, k))
    on = run_simulation(_spec(simkw, fails, k, collect=True))
    _assert_same_run(off, on)
    assert off.obs is None and on.obs is not None
    oracle = latency_histogram_np(on.delivery_latency)
    assert np.array_equal(np.asarray(on.obs.latency_hist), oracle)
    delivered = int((np.asarray(on.deliver_time) >= 0).sum())
    assert on.obs.total_counted() + on.obs.uncounted == delivered
    assert on.obs.uncounted == 0
    assert on.obs.resend_total == int(np.sum(on.metrics.resends))


def test_dense_path_metrics_exact():
    """The dense (window_slots=None) kernel populates send_step /
    delivery_latency / obs from the same oracle-checked rule."""
    simkw = dict(n_msgs=64, steps=120, window=1, phi=6)
    fails = FailureScenario(crash_s=(5, -1, -1, -1))
    sim = SimConfig(collect_metrics=True, **simkw)
    r = run_simulation(build_spec(BFT1, BFT1, sim, fails))
    assert r.obs is not None
    oracle = latency_histogram_np(r.delivery_latency)
    assert np.array_equal(np.asarray(r.obs.latency_hist), oracle)
    # windowed at full width must agree with dense exactly
    rw = run_simulation(_spec(dict(window_slots=64, chunk_steps=8,
                                   **simkw), fails, 8, collect=True))
    assert np.array_equal(r.delivery_latency, rw.delivery_latency)
    assert np.array_equal(np.asarray(r.obs.latency_hist),
                          np.asarray(rw.obs.latency_hist))


def test_batched_sweep_metrics_exact():
    """Vmapped scenario sweeps: each lane's histogram matches its own
    latency array (per-lane carries through the K=8 fused kernel)."""
    simkw = dict(n_msgs=128, steps=128 // 4 + 60, window=1, phi=6,
                 window_slots=32, chunk_steps=8)
    scenarios = [FailureScenario.none(), GC_STALL,
                 FailureScenario(crash_s=(1, -1, -1, -1)),
                 FailureScenario.crash_fraction(4, 4, 0.33, seed=1)]
    rs = run_simulation_batch(
        [_spec(simkw, f, 8, collect=True) for f in scenarios])
    for r in rs:
        assert np.array_equal(np.asarray(r.obs.latency_hist),
                              latency_histogram_np(r.delivery_latency))
        assert r.obs.uncounted == 0


@pytest.mark.parametrize("name,simkw,fails", FIXTURES[:3], ids=IDS[:3])
def test_delivery_latency_matches_refsim(name, simkw, fails):
    """``SimResult.send_step`` / ``delivery_latency`` are bit-identical
    to the numpy reference machine's mirrors."""
    r = run_simulation(_spec(simkw, fails, 8))
    ref = run_reference(_spec(simkw, fails, 1))
    assert np.array_equal(r.send_step, ref.send_step)
    assert np.array_equal(r.delivery_latency, ref.delivery_latency)


def test_topology_chain_metrics_exact():
    """Chained topology: per-link histograms match per-link latency
    arrays, metrics collection leaves chained results untouched, and
    the refsim topology mirror agrees on the latency arrays."""
    from repro.topology.engine import run_topology
    from repro.topology.graph import Topology
    from repro.topology.refmirror import run_topology_reference

    SIM = SimConfig(n_msgs=96, steps=96 // 4 + 60, window=1, phi=6,
                    window_slots=24, chunk_steps=8)
    SIM_ON = dataclasses.replace(SIM, collect_metrics=True)
    r_off = run_topology(Topology.chain(["a", "b", "c"], BFT1, SIM))
    r_on = run_topology(Topology.chain(["a", "b", "c"], BFT1, SIM_ON))
    ref = run_topology_reference(Topology.chain(["a", "b", "c"], BFT1,
                                                SIM))
    for name in ("a->b", "b->c"):
        a, b = r_on[name].result, r_off[name].result
        _assert_same_run(a, b)
        assert np.array_equal(np.asarray(a.obs.latency_hist),
                              latency_histogram_np(a.delivery_latency))
        rr = ref[name].result
        assert np.array_equal(a.send_step, rr.send_step)
        assert np.array_equal(a.delivery_latency, rr.delivery_latency)


def test_replay_resume_metrics_exact(tmp_path):
    """A resumed replay reproduces send_step/delivery_latency exactly
    (in-flight send times cross the checkpoint via the serialized
    mirror), and its segment-scoped histogram matches the numpy oracle
    restricted to post-checkpoint deliveries."""
    from repro.replay import record_simulation, replay
    from repro.replay.trace import RunTrace

    simkw = dict(n_msgs=96, steps=120, window=1, phi=6,
                 window_slots=24, chunk_steps=8)
    spec = _spec(simkw, FailureScenario(crash_s=(16, -1, -1, -1)), 8,
                 collect=True)
    r0, trace = record_simulation(spec)
    path = os.path.join(str(tmp_path), "trace.npz")
    trace.save(path)
    loaded = RunTrace.load(path)
    for c0, c1 in zip(trace.checkpoints, loaded.checkpoints):
        assert (c0.send_step is None) == (c1.send_step is None)
        if c0.send_step is not None:
            assert np.array_equal(c0.send_step, c1.send_step)
    mid = int(trace.boundaries()[len(trace.boundaries()) // 2])
    rr = replay(loaded, mid)[0]
    _assert_same_run(r0, rr)
    seg_lat = np.where(np.asarray(r0.deliver_time) >= mid,
                       np.asarray(r0.delivery_latency), -1)
    assert np.array_equal(np.asarray(rr.obs.latency_hist),
                          latency_histogram_np(seg_lat))
    assert rr.obs.uncounted == 0


# --- overhead: the zero-new-transfers contract ---------------------------

@pytest.mark.parametrize("k", [1, 8])
def test_metrics_overhead_contract(k):
    """collect_metrics=True adds 0 dispatches, 0 implicit transfers and
    0 warm recompiles (≤1 extra compile cold) vs metrics-off — asserted
    via the analysis sanitizer's dispatch contract."""
    from repro.analysis import dispatch_contract, sanitized

    # shape unique to this test so the cold-compile deltas are real
    simkw = dict(n_msgs=136, steps=136 // 4 + 40, window=1, phi=6,
                 window_slots=34, chunk_steps=4)
    off = _spec(simkw, FailureScenario.none(), k)
    on = _spec(simkw, FailureScenario.none(), k, collect=True)

    t0 = chunk_trace_count()
    run_simulation(off)
    cold_off = chunk_trace_count() - t0
    t0 = chunk_trace_count()
    run_simulation(on)
    cold_on = chunk_trace_count() - t0
    assert cold_on <= cold_off + 1, (cold_on, cold_off)

    with sanitized(dispatch_contract(off, warm=True)) as rep_off:
        r_off = run_simulation(off)
    with sanitized(dispatch_contract(on, warm=True)) as rep_on:
        r_on = run_simulation(on)
    _assert_same_run(r_off, r_on)
    assert rep_on.dispatches == rep_off.dispatches
    assert rep_on.transfers == () and rep_off.transfers == ()


def test_metrics_off_jaxprs_byte_identical():
    """Turning collect_metrics on and back off rebuilds byte-identical
    programs: the flag is a static Python branch, so disabled metrics
    cannot perturb staging (same cache key, same jaxpr text)."""
    import jax
    import jax.numpy as jnp

    from repro.core.simulator import (_build_chunk, _build_run,
                                      _fail_arrays, _init_state, _neutral)

    sim = SimConfig(n_msgs=48, steps=60, window=1, phi=6,
                    window_slots=12, chunk_steps=4)
    spec_off = build_spec(BFT1, BFT1, sim)
    spec_on = dataclasses.replace(spec_off, collect_metrics=True)
    spec_off2 = dataclasses.replace(spec_on, collect_metrics=False)
    assert spec_off2 == spec_off        # compile-cache key equality

    nspec = _neutral(spec_off)
    nspec2 = _neutral(spec_off2)
    assert nspec2 == nspec
    fails, state = _fail_arrays(spec_off), _init_state(nspec, 12)
    cspec = dataclasses.replace(nspec, steps=0)
    cspec2 = dataclasses.replace(nspec2, steps=0)
    t0 = jnp.int32(0)
    jp = str(jax.make_jaxpr(_build_chunk(cspec, 12, 4, True))(
        fails, state, t0))
    jp2 = str(jax.make_jaxpr(_build_chunk(cspec2, 12, 4, True))(
        fails, state, t0))
    assert jp == jp2
    assert str(jax.make_jaxpr(_build_run(nspec))(fails)) == \
        str(jax.make_jaxpr(_build_run(nspec2))(fails))
    # and the metrics-on program is genuinely different (the fabric
    # exists when asked for)
    mspec = dataclasses.replace(cspec, collect_metrics=True)
    from repro.obs.metrics import init_metrics_carry
    jp_on = str(jax.make_jaxpr(_build_chunk(mspec, 12, 4, True))(
        fails, (state, init_metrics_carry(12)), t0))
    assert jp_on != jp


# --- unit: buckets and percentiles ---------------------------------------

def test_bucket_edges_and_percentiles():
    lat = np.array([0, 0, 1, 2, 3, 4, 65535, 65536, 70000, -1])
    hist = latency_histogram_np(lat)
    assert int(hist.sum()) == 9                       # -1 excluded
    assert hist[0] == 2                               # lat 0
    assert hist[1] == 1                               # lat 1
    assert hist[2] == 2                               # lat 2,3 -> [2,4)
    assert hist[3] == 1                               # lat 4 -> [4,8)
    assert hist[NUM_LATENCY_BUCKETS - 2] == 1         # 65535 < 2^16
    assert hist[NUM_LATENCY_BUCKETS - 1] == 2         # >= 2^16 sink
    assert bucket_label(0) == "0"
    assert bucket_label(1) == "1"
    assert bucket_label(2) == "2-3"
    assert bucket_label(3) == "4-7"
    assert bucket_label(NUM_LATENCY_BUCKETS - 1) == ">=65536"
    assert percentile_from_hist(np.zeros(NUM_LATENCY_BUCKETS), 50) == -1
    one = np.zeros(NUM_LATENCY_BUCKETS, dtype=int)
    one[0] = 100
    assert percentile_from_hist(one, 99) == 0
    one[3] = 1    # 1 of 101 deliveries in [4,8): p50 still bucket 0
    assert percentile_from_hist(one, 50) == 0
    assert percentile_from_hist(one, 100) == 8        # upper edge of [4,8)
    sink = np.zeros(NUM_LATENCY_BUCKETS, dtype=int)
    sink[-1] = 5
    assert percentile_from_hist(sink, 50) == 65536    # sink lower bound


def test_latency_bucket_device_matches_np():
    import jax.numpy as jnp

    from repro.obs.metrics import latency_bucket, latency_bucket_np

    lat = np.array([0, 1, 2, 3, 7, 8, 1023, 1024, 65535, 65536, 10 ** 6])
    assert np.array_equal(np.asarray(latency_bucket(jnp.asarray(lat))),
                          latency_bucket_np(lat))


# --- tracer + report -----------------------------------------------------

def test_tracer_spans_and_chrome_schema():
    tr = SpanTracer()
    with tracing(tr):
        with obs_span("outer", cat="test", k=1):
            with obs_span("inner", cat="test"):
                pass
    assert tr.count("outer") == tr.count("inner") == 1
    assert tr.total_ns("outer") >= tr.total_ns("inner")
    doc = tr.to_chrome_trace()
    assert validate_chrome_trace(doc) == []
    assert {e["name"] for e in doc["traceEvents"]} == {"outer", "inner"}
    assert "outer" in tr.summary()
    # disabled tracing records nothing and takes no clock samples
    from repro.obs.tracer import obs_begin
    assert obs_begin() is None


def test_span_annotations_close_in_lifo_order():
    """Each named span is a profiler annotation while a tracer is
    installed; ending an outer span first closes any inner one an
    exception left open, so the annotations always nest."""
    from repro.obs.tracer import _annotations, obs_begin, obs_end

    tr = SpanTracer()
    with tracing(tr):
        outer = obs_begin("outer")
        obs_begin("abandoned")          # its obs_end never runs
        inner = obs_begin("inner")
        assert len(_annotations()) == 3
        obs_end(outer, "outer")
        assert _annotations() == []
        obs_end(inner, "inner")         # already closed: recorded only
        renamed = obs_begin("dispatch")
        obs_end(renamed, "compile")
    assert tr.names() == ["outer", "inner", "compile"]
    assert _annotations() == []
    assert obs_begin("x") is None       # no tracer: nothing begins


def test_program_spans_land_on_the_profiler_clock(tmp_path):
    """With the JAX profiler on, the engine's spans are host events of
    the trace, nested as the tracer recorded them."""
    import glob

    import jax

    from repro.core.simulator import _run_windowed_batch
    from repro.stream import ArrivalProcess, build_stream_spec

    spec = build_stream_spec(
        RSMConfig.bft(1), RSMConfig.bft(1),
        SimConfig(window=1, phi=6, window_slots="auto", chunk_steps=8,
                  superchunk=4),
        ArrivalProcess(kind="constant", rate=4.0), 128)

    class Sink:
        def on_chunk(self, *a):
            pass

        def on_final(self, *a):
            pass

    _run_windowed_batch([spec], drain_sink=Sink())      # compile first
    tr = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing(tr):
            _run_windowed_batch([spec], drain_sink=Sink())
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    seen = [ev.name for p in data.planes for ln in p.lines
            for ev in ln.events]
    for name in ("run", "prologue", "plan", "dispatch", "drain_wait",
                 "unpack", "sink_chunk", "final_flush"):
        assert seen.count(name) == tr.count(name), name


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({}) != []
    bad = {"traceEvents": [{"name": "x", "cat": "c", "ph": "B",
                            "ts": 0, "dur": -1, "pid": 0, "tid": 0,
                            "args": {}}]}
    problems = validate_chrome_trace(bad)
    assert any("ph" in p for p in problems)
    assert any("negative dur" in p for p in problems)


def test_engine_emits_canonical_spans():
    """A windowed run records run/compile-or-dispatch/drain_wait/
    final_flush; a chained topology adds run_topology + plan_floors."""
    tr = SpanTracer()
    simkw = dict(n_msgs=96, steps=96 // 4 + 40, window=1, phi=6,
                 window_slots=24, chunk_steps=8)
    with tracing(tr):
        run_simulation(_spec(simkw, FailureScenario.none(), 8))
    names = set(tr.names())
    assert {"run", "drain_wait", "final_flush"} <= names
    assert names & {"compile", "dispatch"}
    assert 0.0 <= tr.drain_overlap_ratio() <= 1.0
    for s in tr.spans:
        if s.name == "drain_wait":
            assert "overlapped" in s.args

    from repro.topology.graph import Topology
    _, report = run_reported_topology(Topology.chain(
        ["a", "b", "c"], BFT1,
        SimConfig(n_msgs=64, steps=120, window=1, phi=6,
                  window_slots=16, chunk_steps=8)))
    tnames = {e["name"] for e in report.chrome_trace["traceEvents"]}
    assert {"run_topology", "plan_floors", "run"} <= tnames


def test_run_report_roundtrip(tmp_path):
    simkw = dict(n_msgs=96, steps=96 // 4 + 40, window=1, phi=6,
                 window_slots=24, chunk_steps=8)
    _, report = run_reported(_spec(simkw, GC_STALL, 8))
    assert report.validate() == []
    assert "link" in report.percentile_table()
    prefix = os.path.join(str(tmp_path), "report")
    paths = report.save(prefix)
    assert os.path.exists(paths["json"]) and os.path.exists(paths["npz"])
    back = RunReport.load(prefix)
    assert back.validate() == []
    assert np.array_equal(back.obs["link"].latency_hist,
                          report.obs["link"].latency_hist)
    assert np.array_equal(back.latency["link"], report.latency["link"])
    assert back.spans["drain_overlap_ratio"] == \
        report.spans["drain_overlap_ratio"]
    # json side is self-contained (no numpy types leak through)
    json.dumps(back.to_json_dict())


def test_report_requires_metrics():
    simkw = dict(n_msgs=48, steps=60, window=1, phi=6,
                 window_slots=12, chunk_steps=4)
    r = run_simulation(_spec(simkw, FailureScenario.none(), 1))
    from repro.obs.report import report_from_results
    with pytest.raises(ValueError, match="collect_metrics"):
        report_from_results([r], SpanTracer())


def test_obs_selftest_cli(tmp_path):
    """The CI gate: ``python -m repro.obs --selftest`` exits 0 and
    leaves the RunReport + Perfetto trace artifacts."""
    from repro.obs.__main__ import main

    out = os.path.join(str(tmp_path), "obs_out")
    assert main(["--selftest", "--out", out]) == 0
    assert os.path.exists(os.path.join(out, "report.json"))
    assert os.path.exists(os.path.join(out, "report.npz"))
    with open(os.path.join(out, "trace.json")) as f:
        assert validate_chrome_trace(json.load(f)) == []


# --- benchmarks/run.py resilience ---------------------------------------

def _bench_run_module():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import benchmarks.run as br
    return br


def test_bench_run_partial_failure_writes_artifacts(tmp_path,
                                                    monkeypatch, capsys):
    """A section that dies mid-sweep is recorded as failed, its BENCH
    json gets a status stub, the summary json lands anyway, and the
    exit code reflects the partial failure."""
    br = _bench_run_module()
    monkeypatch.chdir(tmp_path)

    def ok_section():
        return "fine"

    def boom():
        raise RuntimeError("sweep died mid-flight")

    monkeypatch.setattr(br, "TABLES", (
        ("good", ok_section, None),
        ("bad", boom, "BENCH_bad.json"),
    ))
    rc = br.main([])
    capsys.readouterr()
    assert rc == 1
    with open("BENCH_summary.json") as f:
        summary = json.load(f)
    assert summary["status"] == "partial"
    by_name = {s["name"]: s for s in summary["sections"]}
    assert by_name["good"]["status"] == "ok"
    assert by_name["bad"]["status"] == "failed"
    assert "sweep died" in by_name["bad"]["error"]
    with open("BENCH_bad.json") as f:
        stub = json.load(f)
    assert stub["status"] == "failed" and stub["rows"] == []


def test_bench_run_obs_attaches_metrics(tmp_path, monkeypatch, capsys):
    """--obs attaches a validated metrics section (histogram +
    percentiles + drain-overlap ratio) to every BENCH json."""
    br = _bench_run_module()
    monkeypatch.chdir(tmp_path)

    def writes_json():
        br._dump_json("BENCH_mini.json", [{"n": 1}])
        return "ok"

    monkeypatch.setattr(br, "TABLES", (
        ("mini", writes_json, "BENCH_mini.json"),))
    orig_section = br.obs_metrics_section
    monkeypatch.setattr(br, "obs_metrics_section",
                        lambda *a, **kw: orig_section(n_msgs=512, k=8))
    rc = br.main(["--obs"])
    capsys.readouterr()
    assert rc == 0
    with open("BENCH_mini.json") as f:
        doc = json.load(f)
    assert doc["rows"] == [{"n": 1}]
    m = doc["metrics"]
    assert m["validated"], m["problems"]
    assert len(m["obs"]["latency_hist"]) == NUM_LATENCY_BUCKETS
    assert m["obs"]["total_counted"] == 512
    assert 0.0 <= m["drain_overlap_ratio"] <= 1.0
    assert "p95" in m["obs"]


# --- acceptance (slow tier) ----------------------------------------------

@pytest.mark.slow
def test_acceptance_100k_superchunk_report():
    """ISSUE 8 acceptance: a 100k-message K=8 run with metrics on
    yields a RunReport whose histogram matches the numpy oracle's
    latency array exactly, with the dispatch count unchanged vs
    metrics-off (≤ ceil(C/K)+2) and a loadable Perfetto trace with
    compile/dispatch/drain spans."""
    sim = SimConfig(n_msgs=100_000, steps=100_000 // 8 + 96, window=8,
                    phi=6, window_slots="auto", chunk_steps=32,
                    superchunk=8, collect_metrics=True)
    spec = build_spec(BFT1, BFT1, sim)
    result, report = run_reported(spec)
    assert report.validate() == []
    o = report.obs["link"]
    assert o.total_counted() == 100_000
    assert np.array_equal(np.asarray(o.latency_hist),
                          latency_histogram_np(result.delivery_latency))
    for q in ("p50", "p95", "p99"):
        assert o.percentiles()[q] >= 0

    n_chunks = -(-spec.steps // spec.chunk_steps)
    bound = -(-n_chunks // 8) + 2
    assert report.meta["chunk_dispatches"] <= bound

    d0 = chunk_dispatch_count()
    off = run_simulation(dataclasses.replace(spec, collect_metrics=False))
    assert report.meta["chunk_dispatches"] == chunk_dispatch_count() - d0
    assert np.array_equal(result.deliver_time, off.deliver_time)

    doc = report.chrome_trace
    assert validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"drain_wait", "final_flush", "run"} <= names
    assert names & {"compile", "dispatch"}
    assert 0.0 <= report.spans["drain_overlap_ratio"] <= 1.0
