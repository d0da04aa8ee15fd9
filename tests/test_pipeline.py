"""Pipelined superchunk engine: fusion, async drains, counters, Pallas.

The windowed engine fuses up to K = ``SimConfig.superchunk`` chunk
bodies into one compiled dispatch and drains a dispatch's K-deep output
queue while the next dispatch computes. The contract under test: **any K
is bit-identical to the synchronous K = 1 loop** — outputs, per-round
metric streams, GC-frontier trajectories, adaptive-growth events,
recorded traces — across every fusion-break boundary (adaptive growth,
dense fallback, recorder checkpoints, commit-floor callbacks,
failure-schedule swaps), while the dispatch and host-sync counters
(`chunk_dispatch_count` / `host_sync_count`) shrink ~K×. The counter
assertions are what the CI smoke relies on — deterministic counts, not
wall time.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import FailureScenario, RSMConfig, SimConfig
from repro.core.quack import stake_quorum_bitmap
from repro.core.simulator import (build_spec, chunk_dispatch_count,
                                  chunk_launch_ahead_count, run_simulation,
                                  run_simulation_batch, superchunk_program)

BFT1 = RSMConfig.bft(1)

OUTPUTS = ("quack_time", "deliver_time", "retry", "recv_has")
METRICS = ("cross_msgs", "intra_msgs", "resends", "acks", "delivered",
           "min_quack_prefix")

GC_STALL = FailureScenario(byz_bcast_partial=(True, False, False, False),
                           bcast_limit=2)
STALL_PLUS_CRASH = FailureScenario(
    byz_bcast_partial=(True, False, False, False), bcast_limit=2,
    crash_r=(-1, 8, -1, -1))

# every fusion-break class is represented: plain rotation, adaptive
# window growth (frontier stalled mid-run), dense-layout fallback, and
# a crashed sender (retransmission election stays busy all run).
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


def _spec(simkw, fails, k):
    sim = SimConfig(debug_checks=True, superchunk=k, **simkw)
    return build_spec(BFT1, BFT1, sim, fails)


def _assert_same(a, b):
    for out in OUTPUTS:
        assert np.array_equal(getattr(a, out), getattr(b, out)), out
    for name in METRICS:
        assert np.array_equal(getattr(a.metrics, name),
                              getattr(b.metrics, name)), name
    assert np.array_equal(a.gc_frontiers, b.gc_frontiers)
    assert a.final_window_slots == b.final_window_slots
    assert a.window_growth_events == b.window_growth_events


@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("name,simkw,fails", FIXTURES, ids=IDS)
def test_superchunk_bit_identical_to_sync(name, simkw, fails, k):
    """K ∈ {2, 8} ≡ K = 1 across every fusion-break class — outputs,
    metric streams, frontier trajectories, growth events."""
    sync = run_simulation(_spec(simkw, fails, 1))
    fused = run_simulation(_spec(simkw, fails, k))
    _assert_same(sync, fused)


def test_superchunk_batch_bit_identical():
    """Fused batched sweeps (per-scenario window bases) ≡ sync sweeps."""
    simkw = dict(n_msgs=128, steps=128 // 4 + 60, window=1, phi=6,
                 window_slots=32, chunk_steps=8)
    scenarios = [FailureScenario.none(), GC_STALL,
                 FailureScenario(crash_s=(1, -1, -1, -1)),
                 FailureScenario.crash_fraction(4, 4, 0.33, seed=1)]
    b1 = run_simulation_batch([_spec(simkw, f, 1) for f in scenarios])
    b8 = run_simulation_batch([_spec(simkw, f, 8) for f in scenarios])
    for sync, fused in zip(b1, b8):
        _assert_same(sync, fused)


def test_dispatch_and_sync_counts_shrink():
    """The CI acceptance observable: at K = 8 the engine issues ~K×
    fewer device dispatches and host syncs than the synchronous loop —
    asserted via the analysis sanitizer's declarative contract
    (``<= ceil(C/K) + 2`` dispatches, 0 implicit transfers, 0 warm
    recompiles), on deterministic counters, not wall time."""
    from repro.analysis import dispatch_contract, sanitized

    simkw = dict(n_msgs=512, steps=512 // 4 + 40, window=1, phi=6,
                 window_slots=256, chunk_steps=4)
    s1 = _spec(simkw, FailureScenario.none(), 1)
    s8 = _spec(simkw, FailureScenario.none(), 8)
    run_simulation(s1), run_simulation(s8)      # warm both programs

    # warm=True adds the zero-recompilation clause; sanitized() raises
    # on any violated ceiling, transfers and syncs included
    with sanitized(dispatch_contract(s1, warm=True)) as rep1:
        r1 = run_simulation(s1)
    with sanitized(dispatch_contract(s8, warm=True)) as rep8:
        r8 = run_simulation(s8)

    _assert_same(r1, r8)
    n_chunks = -(-s1.steps // s1.chunk_steps)
    assert rep1.dispatches == n_chunks          # sync loop: 1 per chunk
    # fused: ~steps/(K*chunk) (+1 for the final unrotated chunk and a
    # partial tail span) — the same ceiling the contract enforces
    assert rep8.dispatches <= -(-n_chunks // 8) + 2, rep8.to_dict()
    assert rep8.host_syncs <= rep8.dispatches + 2   # one drain/dispatch
    assert rep1.host_syncs >= n_chunks              # sync: one per chunk
    assert rep1.transfers == () and rep8.transfers == ()


def test_async_drain_overlap_engages():
    """With a window wide enough for the conservative bound, the engine
    launches dispatch k+1 before draining k (observable: results are
    still exact — this fixture's whole point is that the overlap path
    is the one executing; counters confirm the fused cadence)."""
    simkw = dict(n_msgs=256, steps=256 // 4 + 40, window=1, phi=6,
                 window_slots=128, chunk_steps=4)
    s1 = _spec(simkw, FailureScenario.none(), 1)
    s4 = _spec(simkw, FailureScenario.none(), 4)
    a0 = chunk_launch_ahead_count()
    r1 = run_simulation(s1)
    a1 = chunk_launch_ahead_count()
    r4 = run_simulation(s4)
    _assert_same(r1, r4)
    # the counter tells the overlap path from the sync regime: K = 1
    # never launches ahead, the fused run does
    assert a1 - a0 == 0
    assert chunk_launch_ahead_count() - a1 > 0


def test_launch_ahead_counter_matches_the_plan():
    """Each launch-ahead is one drain that overlapped its successor; a
    plan that names a reason to sync (K = 1, or a span the conservative
    bound cannot clear) is never followed by one."""
    from repro.obs.tracer import SpanTracer, tracing

    simkw = dict(n_msgs=256, steps=256 // 4 + 40, window=1, phi=6,
                 window_slots=32, chunk_steps=4)
    for k in (1, 4):
        tr = SpanTracer()
        a0 = chunk_launch_ahead_count()
        with tracing(tr):
            run_simulation(_spec(simkw, FailureScenario.none(), k))
        ahead = chunk_launch_ahead_count() - a0
        plans = [sp.args for sp in tr.spans if sp.name == "plan"]
        overlapped = [sp for sp in tr.spans if sp.name == "drain_wait"
                      and sp.args["overlapped"]]
        assert ahead == len(overlapped)
        assert len(plans) == tr.count("dispatch") + tr.count("compile")
        # dispatch j launches ahead only if plan j - 1 named no reason
        assert ahead <= sum(p["why_sync"] is None for p in plans[:-1])
        if k == 1:      # the sync regime: nothing ever launches ahead
            assert ahead == 0
            assert {p["why_sync"] for p in plans} == {"k1"}
        else:
            assert "bound" in {p["why_sync"] for p in plans}
            assert plans[-1]["why_sync"] == "last"


def test_step_phases_named_in_program_metadata():
    """The protocol step's phases reach the compiled program's HLO
    metadata as named scopes, so a device trace can attribute time to
    them whatever fusion numbers a compile hands out."""
    simkw = dict(n_msgs=64, steps=64 // 4 + 40, window=1, phi=6,
                 window_slots=32, chunk_steps=4)
    program, args = superchunk_program(
        _spec(simkw, FailureScenario.none(), 2), lanes=2)
    text = program.lower(*args).as_text(debug_info=True)
    for scope in ("c3b_retransmit", "c3b_quorum", "c3b_broadcast",
                  "c3b_send", "c3b_hq", "c3b_ack", "c3b_rotate"):
        assert scope in text, scope


def test_debug_checks_off_still_exact():
    """debug_checks only gates the host mirror assertion — results are
    identical with it off (the benchmark configuration)."""
    simkw = dict(n_msgs=128, steps=128 // 4 + 40, window=1, phi=6,
                 window_slots=32, chunk_steps=4)
    spec_dbg = _spec(simkw, GC_STALL, 8)
    spec_off = dataclasses.replace(spec_dbg, debug_checks=False)
    assert spec_dbg.debug_checks and not spec_off.debug_checks
    _assert_same(run_simulation(spec_dbg), run_simulation(spec_off))


def test_recorder_boundaries_flush_pipeline():
    """Recorded runs are a mandatory host-interaction path (chunk-at-a-
    time, checkpoints flush the pipeline): a trace recorded under K = 8
    with sparse checkpoints is bit-exact with the K = 1 trace, its
    replay reproduces the run, and — because the parent compiled every
    program the tail reuses — the replay retraces nothing."""
    from repro.replay import record_simulation, replay

    simkw = dict(n_msgs=96, steps=120, window=1, phi=6,
                 window_slots=24, chunk_steps=8)
    r1, tr1 = record_simulation(_spec(simkw, FailureScenario.none(), 1),
                                every=2)
    r8, tr8 = record_simulation(_spec(simkw, FailureScenario.none(), 8),
                                every=2)
    _assert_same(r1, r8)
    assert [c.t for c in tr1.checkpoints] == [c.t for c in tr8.checkpoints]
    for c1, c8 in zip(tr1.checkpoints, tr8.checkpoints):
        assert np.array_equal(c1.bases, c8.bases)
        assert np.array_equal(c1.bases_hist, c8.bases_hist)
        assert np.array_equal(c1.floors, c8.floors)
        for name in type(c1.state)._fields:
            assert np.array_equal(getattr(c1.state, name),
                                  getattr(c8.state, name)), name
        m1, m8 = c1.metrics(), c8.metrics()
        for name in METRICS:
            assert np.array_equal(getattr(m1, name),
                                  getattr(m8, name)), name
    mid = tr8.boundaries()[len(tr8.boundaries()) // 2]
    from repro.core.simulator import chunk_trace_count
    before = chunk_trace_count()
    replayed = replay(tr8, int(mid))[0]
    assert chunk_trace_count() == before    # zero-recompilation contract
    for out in OUTPUTS:
        assert np.array_equal(getattr(replayed, out), getattr(r8, out)), out


def test_commit_floor_boundaries_stay_synchronous():
    """Chained topologies (commit-floor callbacks every chunk) are a
    mandatory host-interaction boundary: K = 8 ≡ K = 1 including the
    per-chunk floor history."""
    from repro.topology import Topology, LinkSpec, run_topology

    def chain(k):
        return Topology(
            clusters={"a": BFT1, "b": BFT1, "c": BFT1},
            links=(LinkSpec("a->b", "a", "b"),
                   LinkSpec("b->c", "b", "c", upstream="a->b")),
            sim=SimConfig(n_msgs=96, steps=160, window=1, phi=6,
                          window_slots=24, chunk_steps=8, superchunk=k,
                          debug_checks=True))

    r1, r8 = run_topology(chain(1)), run_topology(chain(8))
    for name in ("a->b", "b->c"):
        for out in OUTPUTS:
            assert np.array_equal(getattr(r1[name].result, out),
                                  getattr(r8[name].result, out)), out
        assert np.array_equal(r1[name].commit_floors,
                              r8[name].commit_floors)
        assert np.array_equal(r1[name].result.gc_frontiers,
                              r8[name].result.gc_frontiers)


def test_fail_schedule_swap_breaks_fusion_exactly():
    """A mid-stream schedule edit (replay injection) lands on a fused
    run exactly as on the synchronous loop: replayed-with-injection ≡
    from-scratch merged schedule, for a superchunk=8 trace."""
    from repro.core.simulator import spec_with_failures
    from repro.replay import Injection, record_simulation, replay

    crash = FailureScenario(crash_s=(16, -1, -1, -1))
    simkw = dict(n_msgs=96, steps=120, window=1, phi=6,
                 window_slots=24, chunk_steps=8)
    spec = _spec(simkw, FailureScenario.none(), 8)
    _, trace = record_simulation(spec)
    edited = replay(trace, 16, [Injection(at_step=16, failures=crash)])[0]
    # from-scratch: crash in force from round 16 == crash masks with the
    # pre-16 prefix unaffected (crash_s=16 fires at round 16 exactly)
    scratch = run_simulation(spec_with_failures(spec, crash))
    for out in OUTPUTS:
        assert np.array_equal(getattr(edited, out),
                              getattr(scratch, out)), out


def test_superchunk_respects_strict_overflow():
    """Strict (adaptive_window=False) overflow still raises at the same
    boundary under fusion — the in-graph guard stops the span and the
    host re-checks exactly where K = 1 would have raised."""
    for k in (1, 8):
        sim = SimConfig(n_msgs=64, steps=40, window=4, phi=6,
                        window_slots=8, chunk_steps=4,
                        adaptive_window=False, superchunk=k)
        with pytest.raises(ValueError, match="window overflow"):
            run_simulation(build_spec(BFT1, BFT1, sim))


# --- Pallas QUACK kernel wiring -----------------------------------------

def test_stake_quorum_bitmap_pallas_matches_jnp():
    """Unit equivalence: the Pallas quorum kernel (interpret mode off-
    TPU) and the jnp einsum path agree exactly — quacked/lost bitmaps
    and contiguous quacked prefix."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    # 600: wider than one 512 block but not a multiple of it — the
    # padded-kernel path every auto/grown/dense window width exercises
    for s, r, w in [(4, 4, 24), (3, 5, 16), (2, 3, 130), (2, 3, 600)]:
        claims = jnp.asarray(rng.rand(s, r, w) < 0.5)
        comp = jnp.asarray(rng.rand(s, r, w) < 0.3)
        stakes = jnp.asarray(rng.randint(1, 5, size=r).astype(np.float32))
        jn = stake_quorum_bitmap(claims, comp, stakes, 3.0, 2.0,
                                 use_pallas=False)
        pl = stake_quorum_bitmap(claims, comp, stakes, 3.0, 2.0,
                                 use_pallas=True)
        for a, b in zip(jn, pl):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # the lost-free variant (the hot loop's step-5 shape: the
        # complaints stream is cut at the kernel boundary)
        for up in (False, True):
            q, lost, p = stake_quorum_bitmap(claims, comp, stakes, 3.0,
                                             2.0, use_pallas=up,
                                             need_lost=False)
            assert lost is None
            assert np.array_equal(np.asarray(q), np.asarray(jn[0]))
            assert np.array_equal(np.asarray(p), np.asarray(jn[2]))


def test_pallas_quack_run_equivalence():
    """A windowed AND a dense run with use_pallas_quack=True are bit-
    identical to the jnp-path runs (the kernel sits inside the scan)."""
    simkw = dict(n_msgs=16, steps=40, window=1, phi=6, window_slots=16,
                 chunk_steps=4)
    spec = _spec(simkw, FailureScenario(crash_s=(1, -1, -1, -1)), 2)
    spec_p = dataclasses.replace(spec, use_pallas_quack=True)
    _assert_same(run_simulation(spec), run_simulation(spec_p))
    dense = dataclasses.replace(spec, window_slots=0, chunk_steps=0)
    dense_p = dataclasses.replace(dense, use_pallas_quack=True)
    rd, rdp = run_simulation(dense), run_simulation(dense_p)
    for out in OUTPUTS:
        assert np.array_equal(getattr(rd, out), getattr(rdp, out)), out


# --- randomized equivalence ---------------------------------------------

def _random_scenario(rng, n_s, n_r):
    crash_s = [-1] * n_s
    crash_r = [-1] * n_r
    byz_recv = [False] * n_r
    byz_low = [False] * n_r
    byz_partial = [False] * n_r
    if rng.rand() < 0.7:
        crash_s[rng.randint(n_s)] = int(rng.randint(0, 10))
    kind = rng.choice(["none", "crash", "byz_drop", "ack_low",
                       "bcast_partial"])
    j = rng.randint(n_r)
    if kind == "crash":
        crash_r[j] = int(rng.randint(0, 10))
    elif kind == "byz_drop":
        byz_recv[j] = True
    elif kind == "ack_low":
        byz_low[j] = True
    elif kind == "bcast_partial":
        byz_partial[j] = True
    return FailureScenario(
        crash_s=tuple(crash_s), crash_r=tuple(crash_r),
        byz_recv_drop=tuple(byz_recv), byz_ack_low=tuple(byz_low),
        byz_bcast_partial=tuple(byz_partial),
        bcast_limit=int(rng.randint(1, 3)))


@pytest.mark.parametrize("seed", range(6))
def test_property_superchunk_equals_sync_seeded(seed):
    """Hypothesis-free seeded twin of the property below, so the fused ≡
    sync invariant executes even where hypothesis is unavailable."""
    rng = np.random.RandomState(seed)
    fails = _random_scenario(rng, 4, 4)
    k = int(rng.choice([2, 3, 8]))
    simkw = dict(n_msgs=48, steps=160, window=1, phi=6,
                 window_slots=int(rng.choice([12, 16, 24])),
                 chunk_steps=int(rng.choice([4, 8])))
    _assert_same(run_simulation(_spec(simkw, fails, 1)),
                 run_simulation(_spec(simkw, fails, k)))


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 2 ** 16), k=st.integers(2, 8),
           chunk=st.sampled_from([4, 8, 16]),
           w=st.sampled_from([12, 16, 24]))
    def test_property_superchunk_equals_sync(seed, k, chunk, w):
        """Property: for random fusion depth K, chunk length, window
        width and failure schedule, the fused engine ≡ the synchronous
        loop bit-for-bit (growth/dense-fallback boundaries included)."""
        rng = np.random.RandomState(seed)
        fails = _random_scenario(rng, 4, 4)
        simkw = dict(n_msgs=48, steps=160, window=1, phi=6,
                     window_slots=w, chunk_steps=chunk)
        _assert_same(run_simulation(_spec(simkw, fails, 1)),
                     run_simulation(_spec(simkw, fails, k)))
except ImportError:                                   # pragma: no cover
    pass
