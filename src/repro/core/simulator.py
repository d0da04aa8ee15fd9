"""Vectorized PICSOU simulator — windowed streaming core (``jax.lax.scan``).

The simulator executes the *full* protocol of §4–§5 — round-robin / DSS
send scheduling, receiver rotation, intra-RSM broadcast, cumulative +
phi-list acknowledgements, QUACK formation, duplicate-complaint loss
detection, communication-free retransmitter election, GC with the
highest-quacked metadata defence, stake weighting and LCM-scaled
retransmission rotation — as array state transitions, one scan step per
synchronous round (one cross-RSM RTT).

Per-message state lives in a **sliding window**: each message-indexed array
holds ``W = spec.window_slots`` columns covering absolute sequence numbers
``[base, base + W)``. The run is split into compiled chunks of
``spec.chunk_steps`` rounds; at the end of each chunk the GC frontier
(``gc.gc_frontier_device`` — the prefix both sides may forget, §4.3) is
computed *in-graph* and the ring buffers rotate past it on device
(``lax.dynamic_slice`` shift with ``base`` carried as traced scan state).
The retired columns' quack/deliver/retry/recv outputs leave the device
through a bounded O(W) output queue (``ChunkQueue``) that the host drains
once per chunk — the scan state itself never makes a host round-trip until
the final flush. Failure-free, the frontier tracks the stream, so device
state and compile time are O(W) — *independent of the stream length M* —
which is exactly the paper's P1 constant-metadata invariant applied to the
simulator itself. The dense path (``window_slots == 0``) is the same step
function instantiated at ``base=0, W=M`` with no rotation, and the two are
bit-identical wherever the window is wide enough to hold every in-flight
message (``tests/test_windowed.py``).

Window overflow (a Byzantine stall pinning the frontier while originals
keep dispatching) no longer fails the run: with
``SimConfig.adaptive_window`` (the default) the window grows 2x — the
chunk program is re-instantiated at the wider W and the scan state
migrated on device — and when the required W would reach M the run falls
back to the dense kernel automatically (``gc.grow_window``). Setting
``adaptive_window=False`` restores the strict ``ValueError``.

Because ``base`` is traced state, the windowed chunk also ``jax.vmap``s:
``run_simulation_batch`` executes windowed specs with **per-scenario
window bases**, so whole failure sweeps (fig8/fig9) run windowed *and*
batched in one compilation instead of falling back to the O(M) dense
kernel.

Semantics of a round ``t`` (matching Figure 3/4/5/6 of the paper):
  1. intra-RSM broadcasts queued at t-1 land;
  2. retransmissions are declared/elected from knowledge as of t-1 and the
     corresponding resends are put on the wire;
  3. scheduled original sends for round t are put on the wire; direct sends
     land at their receiver (unless dropped) and queue a broadcast;
  4. every alive receiver acks (cumulative counter + phi-list + implicit
     duplicate-cum complaint) to its rotating target sender; senders fold
     the ack into their knowledge; QUACK / GC state advances.

Failure masks are traced inputs (``FailArrays``), not compile-time
constants, so one compilation serves every failure scenario of a given
shape — and ``run_simulation_batch`` ``jax.vmap``s the same step over a
stack of scenarios for one-compilation sweeps.

The pure-python oracle in ``refsim.py`` mirrors this loop (including the
GC-frontier trajectory) unvectorized; ``tests/test_simulator.py`` and
``tests/test_windowed.py`` cross-check them step by step.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import (MetricsBlock, ObsMetrics, init_metrics_carry,
                           migrate_dense_metrics, obs_from_carry,
                           obs_from_final, pad_metrics,
                           resume_metrics_carry, rotate_metrics,
                           snapshot_metrics, update_metrics)
from ..obs.tracer import obs_begin, obs_end
from . import scheduler as sched
from .gc import gc_frontier_device, grow_window, resolve_window_slots
from .quack import (claim_bitmask, missing_below_horizon,
                    stake_quorum_bitmap, weighted_quorum_prefix)
from .snapshot import (WINDOW_FILLS as _WINDOW_FILLS, device_state,
                       host_state, pad_window, window_shapes
                       as _window_shapes)
from .types import (FailureScenario, RSMConfig, SimConfig,
                    lcm_scale_factors)

__all__ = ["SimSpec", "SimResult", "FailArrays", "build_spec",
           "run_simulation", "run_simulation_batch",
           "require_uniform_batch", "ChunkCheckpoint", "WindowGrowthEvent",
           "spec_failures", "spec_with_failures", "spec_with_quorum",
           "retire_safety_stakes_ok", "chunk_trace_count",
           "chunk_dispatch_count", "chunk_launch_ahead_count",
           "host_sync_count"]

# plain Python ints, not jnp scalars: a module-level jnp call would
# initialize the JAX backend at import time (analysis: import-time-jnp);
# weak-typed ints promote to int32 inside the step exactly the same.
_NEVER_STEP = 2 ** 30     # orig_step pad for window slots beyond the stream
_BIG = 2 ** 30


@dataclasses.dataclass(frozen=True)
class SimSpec:
    """Fully-resolved, static simulation plan (hashable closure inputs)."""

    n_s: int
    n_r: int
    m: int
    steps: int
    phi: int
    quack_thresh: float      # u_r + 1 (stake units)
    dup_thresh: float        # r_r + 1 (stake units); 1 in CFT mode
    hq_thresh: float         # r_s + 1 (stake units)
    stakes_s: Tuple[float, ...]
    stakes_r: Tuple[float, ...]
    orig_sender: Tuple[int, ...]      # (M,)
    orig_recv: Tuple[int, ...]        # (M,)
    orig_step: Tuple[int, ...]        # (M,) dispatch round of original send
    rs_seq: Tuple[int, ...]           # retransmit sender rotation sequence
    rr_seq: Tuple[int, ...]           # retransmit receiver rotation sequence
    crash_s: Tuple[int, ...]
    crash_r: Tuple[int, ...]
    byz_send_drop: Tuple[bool, ...]
    byz_recv_drop: Tuple[bool, ...]
    byz_ack_advance: Tuple[int, ...]
    byz_ack_low: Tuple[bool, ...]
    byz_bcast_partial: Tuple[bool, ...]
    bcast_limit: int
    # Byzantine adversary palette (repro.adversary). Optional with None
    # defaults so specs recorded by older traces deserialize unchanged;
    # None is equivalent to the neutral mask everywhere.
    byz_equiv_send: Optional[Tuple[bool, ...]] = None    # (n_s,)
    byz_hq_advance: Optional[Tuple[int, ...]] = None     # (n_s,)
    byz_ack_stale: Optional[Tuple[bool, ...]] = None     # (n_r,)
    drop_pair: Optional[Tuple[Tuple[bool, ...], ...]] = None  # (n_s, n_r)
    window_slots: int = 0             # 0 => dense (full-M) state
    chunk_steps: int = 0              # rounds per compiled chunk (windowed)
    adaptive_window: bool = True      # grow W / dense-fallback on overflow
    superchunk: int = 8               # fused chunks per dispatch (pipeline)
    debug_checks: bool = False        # host-side mirror assertions per drain
    use_pallas_quack: bool = False    # QUACK quorums via the Pallas kernel
    collect_metrics: bool = False     # in-graph obs fabric (repro.obs)

    def scan_state_nbytes(self) -> int:
        """Device bytes of the per-round scan state (the P1 footprint).

        Derived from ``jax.eval_shape`` of the actual carried ``SimState``
        so it cannot drift from the implementation
        (``tests/test_windowed.py`` verifies it against the state a real
        run carries).
        """
        w = self.window_slots or self.m
        state = jax.eval_shape(lambda: _init_state(self, w))
        return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(state))


class FailArrays(NamedTuple):
    """Per-scenario traced inputs (one compile per *shape*).

    Mostly failure masks; ``commit_floor`` is the commit-gated dispatch
    boundary for chained topologies: message ``k`` may only be originated
    once ``k < commit_floor`` (its entry is committed on the source RSM's
    log). A standalone link is fully committed from the start
    (``commit_floor == m``), which reduces the gate to a no-op; the
    topology engine raises a downstream link's floor between chunks as
    its upstream link retires delivered prefix.
    """

    crash_s: jnp.ndarray           # (n_s,) int32, -1 = never
    crash_r: jnp.ndarray           # (n_r,) int32
    byz_send_drop: jnp.ndarray     # (n_s,) bool
    byz_recv_drop: jnp.ndarray     # (n_r,) bool
    byz_ack_advance: jnp.ndarray   # (n_r,) int32
    byz_ack_low: jnp.ndarray       # (n_r,) bool
    byz_bcast_partial: jnp.ndarray  # (n_r,) bool
    bcast_limit: jnp.ndarray       # () int32
    commit_floor: jnp.ndarray      # () int32 — dispatch gate (abs seqno)
    # adversary palette (repro.adversary)
    byz_equiv_send: jnp.ndarray    # (n_s,) bool — resends equivocate
    byz_hq_advance: jnp.ndarray    # (n_s,) int32 — §4.3 hq-piggyback lie
    byz_ack_stale: jnp.ndarray     # (n_r,) bool — replays previous ack
    drop_pair: jnp.ndarray         # (n_s, n_r) bool — selective drops
    # quorum weights/thresholds are traced too, so a mid-stream stake
    # re-weight / membership change (replay Injection) swaps them with
    # zero recompilation — the compiled programs never close over them
    stakes_s: jnp.ndarray          # (n_s,) float32
    stakes_r: jnp.ndarray          # (n_r,) float32
    quack_thresh: jnp.ndarray      # () float32 — u_r + 1 (stake units)
    dup_thresh: jnp.ndarray        # () float32 — r_r + 1
    hq_thresh: jnp.ndarray         # () float32 — r_s + 1


class SimState(NamedTuple):
    recv_has: jnp.ndarray      # (n_r, W) bool — receiver truly holds slot
    bcast_q: jnp.ndarray       # (n_r, W) bool — queued broadcast for t+1
    bcast_done: jnp.ndarray    # (n_r, W) bool
    orig_sent: jnp.ndarray     # (W,) bool — original dispatch attempted
    known: jnp.ndarray         # (n_s, n_r, W) bool — j's claims known to l
    complaint: jnp.ndarray     # (n_s, n_r, W) bool — j's last complaint to l
    repeat_c: jnp.ndarray      # (n_s, n_r, W) bool — complained twice to l
    last_cum: jnp.ndarray      # (n_s, n_r) int32 (absolute counts)
    retry: jnp.ndarray         # (n_s, W) int32
    quack_time: jnp.ndarray    # (n_s, W) int32, -1 = not yet
    deliver_time: jnp.ndarray  # (W,) int32, -1 = not yet
    hq_reports: jnp.ndarray    # (n_r, n_s) int32 (absolute seqnos)
    ack_floor: jnp.ndarray     # (n_r,) int32 (absolute counts)
    base: jnp.ndarray          # () int32 — absolute seqno of window col 0
    retired_delivered: jnp.ndarray  # () int32 — delivered among retired


class StepMetrics(NamedTuple):
    cross_msgs: jnp.ndarray     # direct cross-RSM data copies this round
    intra_msgs: jnp.ndarray     # broadcast copies this round
    resends: jnp.ndarray        # retransmissions this round
    acks: jnp.ndarray           # ack messages this round
    delivered: jnp.ndarray      # cumulative messages delivered
    min_quack_prefix: jnp.ndarray  # min honest-sender quacked prefix


class ChunkQueue(NamedTuple):
    """Bounded device-side output queue, drained by the host once per chunk.

    Holds the pre-rotation window outputs plus (base, count): columns
    ``[0, count)`` are the slots this chunk's in-graph rotation retired,
    covering absolute sequence numbers ``[base, base + count)``. O(W)
    regardless of stream length — the only per-chunk device->host traffic
    besides the round metrics.
    """

    quack_time: jnp.ndarray    # (n_s, W) pre-rotation
    deliver_time: jnp.ndarray  # (W,)
    retry: jnp.ndarray         # (n_s, W)
    recv_has: jnp.ndarray      # (n_r, W)
    base: jnp.ndarray          # () int32 — window base before rotation
    count: jnp.ndarray         # () int32 — slots retired by this rotation


class ChunkCheckpoint(NamedTuple):
    """Host-side snapshot of a batched windowed run at a chunk boundary.

    Captured by ``_run_windowed_batch`` (when given a ``recorder``) right
    before dispatching the chunk that starts at round ``t``, and accepted
    back as its ``resume`` argument: resuming from a checkpoint replays
    the exact remaining chunk stream — same compiled chunk program (the
    batch shape and window width are unchanged, so nothing recompiles),
    same overflow/growth decisions, same drains — and is bit-identical
    to the original run when the failure schedule is unchanged. All
    leaves are host-side numpy (int32/bool), so a device round-trip is
    exact and the tuple serializes losslessly (``repro.replay``).
    """

    t: int                       # next round to execute
    window_slots: int            # window width in force entering the chunk
    bases: np.ndarray            # (B,) per-lane window base
    state: SimState              # batched scan state, numpy leaves
    fails: FailArrays            # masks in force (numpy leaves, stacked)
    floors: np.ndarray           # (B,) commit floors in force
    out_quack: np.ndarray        # (B, n_s, M) drained retired prefix
    out_deliver: np.ndarray      # (B, M)
    out_retry: np.ndarray        # (B, n_s, M)
    out_recv: np.ndarray         # (B, n_r, M)
    # per-chunk (B, c) metric blocks of the rounds already run; shared by
    # reference with the engine loop (capture is O(1), not O(t)) — use
    # ``metrics()`` for the concatenated (B, t) view.
    metric_parts: Tuple[StepMetrics, ...]
    bases_hist: np.ndarray       # (n_boundaries_so_far, B)
    growth_events: Tuple[WindowGrowthEvent, ...]
    # (B, M) dispatch-round mirror (-1 = not yet dispatched) — feeds
    # ``SimResult.delivery_latency`` and seeds the metrics carry across
    # a resume. Trailing + defaulted so traces recorded before it
    # existed still load (``RunTrace._retuple``); ``None`` falls back
    # to the schedule-derived rounds.
    send_step: Optional[np.ndarray] = None

    def metrics(self) -> StepMetrics:
        """Concatenated (B, t) per-round metrics up to this checkpoint."""
        return _concat_metrics(len(self.bases), list(self.metric_parts))


@dataclasses.dataclass(frozen=True)
class WindowGrowthEvent:
    """One adaptive-window growth decision, attributed to its cause.

    In a batched run the whole batch shares one window width, so a single
    frontier-stalled scenario forces growth for every lane — ``scenario``
    records *which* lane overflowed (batch index) and ``step`` the round
    whose dispatch would have outrun the window, instead of the batch
    silently growing W.  ``new_w == m`` with ``dense_migration`` set means
    the run migrated into the dense layout rather than doubling again.
    """

    step: int                # round whose dispatch overflowed the window
    scenario: int            # batch lane that forced the growth
    need: int                # highest in-flight seqno at that round
    old_w: int
    new_w: int
    dense_migration: bool = False
    # what-if fork batches re-attribute tiled lane indices back to
    # (fork, lane) so consumers never see a mixed index space; None for
    # plain (un-forked) runs and for growths inherited from the shared
    # pre-fork prefix.
    fork: Optional[int] = None


@dataclasses.dataclass
class SimResult:
    spec: SimSpec
    metrics: "np.ndarray-like"            # StepMetrics of (T,) arrays
    quack_time: np.ndarray                # (n_s, M)
    deliver_time: np.ndarray              # (M,)
    retry: np.ndarray                     # (n_s, M)
    recv_has: np.ndarray                  # (n_r, M)
    # window base per chunk boundary; dense runs report the trivial
    # single-entry trajectory [0] so every path populates the field.
    gc_frontiers: Optional[np.ndarray] = None
    # window width the run ended with (== m for dense / dense-fallback
    # runs; > spec.window_slots when adaptive growth kicked in).
    final_window_slots: Optional[int] = None
    # adaptive growth provenance: every growth/dense-migration decision
    # the run (or its whole batch — events are shared batch-wide, the
    # ``scenario`` field says which lane forced each) took. Empty when
    # the window never grew.
    window_growth_events: Tuple[WindowGrowthEvent, ...] = ()
    # (M,) round each message's original dispatch actually happened
    # (commit-floor aware; -1 = never dispatched within the run).
    send_step: Optional[np.ndarray] = None
    # (M,) per-message delivery latency: retire step - send step
    # (-1 = not delivered). Populated by dense, windowed and batched
    # paths alike; the numpy refsim mirrors it bit-exactly.
    delivery_latency: Optional[np.ndarray] = None
    # drained in-graph observability summary (repro.obs), present only
    # when the run's SimConfig.collect_metrics was set.
    obs: Optional[ObsMetrics] = None

    # --- derived -------------------------------------------------------
    def completion_step(self) -> int:
        """Round by which every message is QUACKed at every honest sender."""
        honest = _honest_mask(self.spec.crash_s, self.spec.byz_send_drop)
        qt = self.quack_time[honest]
        if qt.size == 0 or (qt < 0).any():
            return -1
        return int(qt.max())

    def delivery_step(self) -> int:
        if (self.deliver_time < 0).any():
            return -1
        return int(self.deliver_time.max())

    def total_cross_msgs(self) -> int:
        return int(np.sum(self.metrics.cross_msgs))

    def total_intra_msgs(self) -> int:
        return int(np.sum(self.metrics.intra_msgs))

    def total_resends(self) -> int:
        return int(np.sum(self.metrics.resends))

    def max_resends_per_msg(self) -> int:
        honest = _honest_mask(self.spec.crash_s, self.spec.byz_send_drop)
        if not honest.any():
            return 0
        return int(self.retry[honest].max())


def _honest_mask(crash, byz_flags) -> np.ndarray:
    crash = np.asarray(crash)
    byz = np.asarray(byz_flags)
    return (crash < 0) & ~byz


def build_spec(sender: RSMConfig, receiver: RSMConfig,
               sim: SimConfig = SimConfig(),
               failures: FailureScenario = FailureScenario.none(),
               use_lcm_scaling: bool = True) -> SimSpec:
    """Resolve schedules + failure masks into a static SimSpec."""
    n_s, n_r, m = sender.n, receiver.n, sim.n_msgs
    st_s = np.asarray(sender.stakes, dtype=np.float64)
    st_r = np.asarray(receiver.stakes, dtype=np.float64)

    orig_sender = sched.sender_assignment(
        sim.scheduler, st_s, m, quantum=sim.quantum, seed=sim.seed)
    orig_recv = sched.receiver_for(
        orig_sender, n_r, recv_stakes=st_r, scheduler=sim.scheduler,
        quantum=sim.quantum, seed=sim.seed + 1)

    # dispatch round of each original send: the i-th message of sender l is
    # sent in round i // window (window sends per sender per round).
    orig_step = np.zeros(m, dtype=np.int64)
    counters = np.zeros(n_s, dtype=np.int64)
    for k in range(m):
        l = orig_sender[k]
        orig_step[k] = counters[l] // max(sim.window, 1)
        counters[l] += 1

    # retransmission rotation sequences (§4.2 unit-stake, §5.3 staked+LCM).
    unit_s = np.allclose(st_s, st_s[0])
    unit_r = np.allclose(st_r, st_r[0])
    if unit_s and unit_r:
        rs_seq = np.arange(n_s, dtype=np.int64)
        rr_seq = np.arange(n_r, dtype=np.int64)
    else:
        psi_s, psi_r = (lcm_scale_factors(st_s.sum(), st_r.sum())
                        if use_lcm_scaling else (1.0, 1.0))
        # quota each replica proportional to (scaled) stake, smoothed.
        q_s = max(n_s, min(4 * n_s, int(np.ceil(
            st_s.sum() * psi_s / max(st_s.min() * psi_s, 1)))))
        q_r = max(n_r, min(4 * n_r, int(np.ceil(
            st_r.sum() * psi_r / max(st_r.min() * psi_r, 1)))))
        rs_seq = sched.dss_sequence(st_s * psi_s, q_s, q_s)
        rr_seq = sched.dss_sequence(st_r * psi_r, q_r, q_r)

    w_slots = resolve_window_slots(
        sim.window_slots, n_s=n_s, n_r=n_r, send_window=sim.window,
        phi=sim.phi, chunk_steps=sim.chunk_steps, m=m)

    return SimSpec(
        n_s=n_s, n_r=n_r, m=m, steps=sim.steps, phi=sim.phi,
        quack_thresh=receiver.quack_threshold,
        dup_thresh=receiver.dup_threshold,
        hq_thresh=max(sender.r + 1, 1),
        stakes_s=tuple(float(x) for x in st_s),
        stakes_r=tuple(float(x) for x in st_r),
        orig_sender=tuple(int(x) for x in orig_sender),
        orig_recv=tuple(int(x) for x in orig_recv),
        orig_step=tuple(int(x) for x in orig_step),
        rs_seq=tuple(int(x) for x in rs_seq),
        rr_seq=tuple(int(x) for x in rr_seq),
        **_failure_fields(failures, n_s, n_r, sim.steps),
        window_slots=w_slots,
        chunk_steps=sim.chunk_steps if w_slots else 0,
        adaptive_window=sim.adaptive_window,
        superchunk=max(sim.superchunk, 1),
        debug_checks=sim.debug_checks,
        use_pallas_quack=sim.use_pallas_quack,
        collect_metrics=sim.collect_metrics,
    )


def _failure_fields(failures: FailureScenario, n_s: int, n_r: int,
                    steps: Optional[int] = None) -> dict:
    """Resolve a FailureScenario into the SimSpec mask fields.

    Validates shapes and ranges up front (clear ``ValueError`` naming
    the field) instead of letting a wrong-length mask fail deep inside
    tracing or a beyond-horizon crash step silently never fire.
    """

    def tup(x, n, default):
        if x is None:
            return tuple([default] * n)
        return tuple(x)

    if failures is None:
        failures = FailureScenario()
    failures.validate(n_s, n_r, steps)
    if failures.drop_pair is None:
        dp = ((False,) * n_r,) * n_s
    else:
        dp = tuple(tuple(bool(x) for x in row)
                   for row in failures.drop_pair)
    return dict(
        crash_s=tup(failures.crash_s, n_s, -1),
        crash_r=tup(failures.crash_r, n_r, -1),
        byz_send_drop=tup(failures.byz_send_drop, n_s, False),
        byz_recv_drop=tup(failures.byz_recv_drop, n_r, False),
        byz_ack_advance=tup(failures.byz_ack_advance, n_r, 0),
        byz_ack_low=tup(failures.byz_ack_low, n_r, False),
        byz_bcast_partial=tup(failures.byz_bcast_partial, n_r, False),
        bcast_limit=failures.bcast_limit,
        byz_equiv_send=tup(failures.byz_equiv_send, n_s, False),
        byz_hq_advance=tup(failures.byz_hq_advance, n_s, 0),
        byz_ack_stale=tup(failures.byz_ack_stale, n_r, False),
        drop_pair=dp,
    )


def spec_with_failures(spec: SimSpec, failures: FailureScenario) -> SimSpec:
    """Overlay a FailureScenario's masks onto an existing spec.

    Everything structural (schedules, thresholds, window config) is kept,
    so the result batches/replays against the original spec's compiled
    chunk — this is how ``repro.replay`` expresses a mid-run schedule
    edit as a full per-lane spec for the stacked ``FailArrays`` rebuild.
    """
    return dataclasses.replace(
        spec, **_failure_fields(failures, spec.n_s, spec.n_r, spec.steps))


def spec_failures(spec: SimSpec) -> FailureScenario:
    """Extract the failure masks of a spec as a FailureScenario."""
    return FailureScenario(
        crash_s=spec.crash_s, crash_r=spec.crash_r,
        byz_send_drop=spec.byz_send_drop,
        byz_recv_drop=spec.byz_recv_drop,
        byz_ack_advance=spec.byz_ack_advance,
        byz_ack_low=spec.byz_ack_low,
        byz_bcast_partial=spec.byz_bcast_partial,
        bcast_limit=spec.bcast_limit,
        byz_equiv_send=spec.byz_equiv_send,
        byz_hq_advance=spec.byz_hq_advance,
        byz_ack_stale=spec.byz_ack_stale,
        drop_pair=spec.drop_pair)


def spec_with_quorum(spec: SimSpec, stakes_s=None, stakes_r=None,
                     quack_thresh=None, dup_thresh=None,
                     hq_thresh=None) -> SimSpec:
    """Re-weight stakes / quorum thresholds on an existing spec.

    The mid-stream reconfiguration primitive: stakes and thresholds are
    *traced* inputs (they ride ``FailArrays``), so the returned spec
    shares the original's compiled programs — a ``fail_schedule`` /
    replay ``Injection`` swap costs zero recompilation. The retransmit
    rotation schedules (``rs_seq``/``rr_seq``) are committed at spec
    build and intentionally kept — re-deriving them would change the
    compiled constants.
    """
    def pick(new, old, n=None):
        if new is None:
            return old
        new = tuple(float(x) for x in new) if n is not None else float(new)
        if n is not None and len(new) != n:
            raise ValueError(f"stake vector has length {len(new)}, "
                             f"expected {n}")
        return new

    return dataclasses.replace(
        spec,
        stakes_s=pick(stakes_s, spec.stakes_s, spec.n_s),
        stakes_r=pick(stakes_r, spec.stakes_r, spec.n_r),
        quack_thresh=pick(quack_thresh, spec.quack_thresh),
        dup_thresh=pick(dup_thresh, spec.dup_thresh),
        hq_thresh=pick(hq_thresh, spec.hq_thresh))


def _fail_arrays(spec: SimSpec) -> FailArrays:
    n_s, n_r = spec.n_s, spec.n_r

    def tup(x, n, default):
        return [default] * n if x is None else x

    dp = (spec.drop_pair if spec.drop_pair is not None
          else np.zeros((n_s, n_r), dtype=bool))
    return FailArrays(
        crash_s=jnp.asarray(spec.crash_s, dtype=jnp.int32),
        crash_r=jnp.asarray(spec.crash_r, dtype=jnp.int32),
        byz_send_drop=jnp.asarray(spec.byz_send_drop, dtype=bool),
        byz_recv_drop=jnp.asarray(spec.byz_recv_drop, dtype=bool),
        byz_ack_advance=jnp.asarray(spec.byz_ack_advance, dtype=jnp.int32),
        byz_ack_low=jnp.asarray(spec.byz_ack_low, dtype=bool),
        byz_bcast_partial=jnp.asarray(spec.byz_bcast_partial, dtype=bool),
        bcast_limit=jnp.int32(max(spec.bcast_limit, 0)),
        commit_floor=jnp.int32(spec.m),
        byz_equiv_send=jnp.asarray(
            tup(spec.byz_equiv_send, n_s, False), dtype=bool),
        byz_hq_advance=jnp.asarray(
            tup(spec.byz_hq_advance, n_s, 0), dtype=jnp.int32),
        byz_ack_stale=jnp.asarray(
            tup(spec.byz_ack_stale, n_r, False), dtype=bool),
        drop_pair=jnp.asarray(dp, dtype=bool).reshape(n_s, n_r),
        stakes_s=jnp.asarray(spec.stakes_s, dtype=jnp.float32),
        stakes_r=jnp.asarray(spec.stakes_r, dtype=jnp.float32),
        quack_thresh=jnp.float32(spec.quack_thresh),
        dup_thresh=jnp.float32(spec.dup_thresh),
        hq_thresh=jnp.float32(spec.hq_thresh),
    )


def _neutral(spec: SimSpec) -> SimSpec:
    """Compile-cache key: failure masks are traced, window handled apart.

    Host-loop knobs (``superchunk``/``debug_checks``) are normalized away
    — they never change a compiled program. ``use_pallas_quack`` IS part
    of the program (it selects the quorum kernel), so it survives — and
    so does ``collect_metrics`` (it adds the metrics carry to the scan).
    Stakes and quorum thresholds are traced inputs (``FailArrays``), so
    they normalize away too — one compiled program serves every stake
    re-weighting, which is what makes mid-stream reconfiguration free.
    (The stake-derived rotation schedules ``rs_seq``/``rr_seq`` remain
    compiled constants and survive.)
    """
    n_s, n_r = spec.n_s, spec.n_r
    return dataclasses.replace(
        spec,
        crash_s=(-1,) * n_s, crash_r=(-1,) * n_r,
        byz_send_drop=(False,) * n_s, byz_recv_drop=(False,) * n_r,
        byz_ack_advance=(0,) * n_r, byz_ack_low=(False,) * n_r,
        byz_bcast_partial=(False,) * n_r, bcast_limit=0,
        byz_equiv_send=(False,) * n_s, byz_hq_advance=(0,) * n_s,
        byz_ack_stale=(False,) * n_r,
        drop_pair=((False,) * n_r,) * n_s,
        stakes_s=(1.0,) * n_s, stakes_r=(1.0,) * n_r,
        quack_thresh=1.0, dup_thresh=1.0, hq_thresh=1.0,
        window_slots=0, chunk_steps=0, adaptive_window=True,
        superchunk=1, debug_checks=False)


def _take_static(table: Tuple[int, ...], pos):
    """``table[pos]`` for a compile-time table of ints and ``0 <= pos <
    len(table)``, with no gather: a TPU runs a gather of per-slot indices
    one index at a time, these are elementwise. The identity table (unit
    stakes) is ``pos`` itself; any other is a select over its positions."""
    # ``table`` is a tuple of the spec, static at trace time
    if table == tuple(range(len(table))):  # analysis: ignore[tracer-branch]
        return pos
    out = jnp.full_like(pos, table[0])
    for p, v in enumerate(table[1:], 1):
        out = jnp.where(pos == p, v, out)
    return out


def _retransmit_lookups(spec: SimSpec, drop_pair, abs_idx, orig_recv, retry):
    """Each (sender, slot)'s retransmission at its retry count, read with
    no gather: whether that sender is the elected one (n_s, W), the
    one-hot of the receiver it goes to (n_s, n_r, W), and whether the
    pair's link drops it (n_s, W).

    Fig. 6: the a-th retransmission of k is sent by the a-th successor of
    the original sender, ``rs_seq[(k + a) mod len]``, to ``rr_seq[(orig
    recv + a) mod len]``; the rotations are static, the drops traced.
    """
    idx_s = jnp.arange(spec.n_s, dtype=jnp.int32)
    idx_r = jnp.arange(spec.n_r, dtype=jnp.int32)
    pos_s = (abs_idx[None, :] + retry) % len(spec.rs_seq)
    pos_r = (orig_recv[None, :] + retry) % len(spec.rr_seq)
    elected = _take_static(spec.rs_seq, pos_s) == idx_s[:, None]
    target = _take_static(spec.rr_seq, pos_r)
    hot = target[:, None, :] == idx_r[None, :, None]
    drop = (hot & drop_pair[:, :, None]).any(axis=1)
    return elected, hot, drop


def _send_lookups(may_send, drop_pair, orig_sender, orig_recv):
    """Each slot's original send read with no gather, through the one-hots
    of its sender (n_s, W) and receiver (n_r, W): whether that sender may
    send (W,) and whether the pair's link drops it (W,)."""
    n_s, n_r = drop_pair.shape
    s_hot = orig_sender[None, :] == jnp.arange(n_s, dtype=jnp.int32)[:, None]
    r_hot = orig_recv[None, :] == jnp.arange(n_r, dtype=jnp.int32)[:, None]
    ok = (s_hot & may_send[:, None]).any(axis=0)
    drop = (s_hot[:, None, :] & r_hot[None, :, :]
            & drop_pair[:, :, None]).any(axis=(0, 1))
    return s_hot, r_hot, ok, drop


def _protocol_step(spec: SimSpec, fail: FailArrays, sched_w, base, w: int):
    """Per-round transition over ``w`` window columns starting at ``base``.

    ``base`` may be a python int (dense: 0) or a traced scalar (windowed);
    all sequence-number arithmetic is absolute so both instantiations run
    the identical protocol.
    """
    n_s, n_r, m = spec.n_s, spec.n_r, spec.m
    phi = spec.phi
    orig_sender, orig_recv, orig_step = sched_w

    # stakes and quorum thresholds ride the traced FailArrays — the
    # compiled program serves every stake re-weighting / membership swap
    stakes_s = fail.stakes_s
    stakes_r = fail.stakes_r

    abs_idx = (base + jnp.arange(w, dtype=jnp.int32)).astype(jnp.int32)
    idx_r = jnp.arange(n_r, dtype=jnp.int32)
    idx_s = jnp.arange(n_s, dtype=jnp.int32)
    honest_r = (fail.crash_r < 0) & ~(fail.byz_recv_drop | fail.byz_ack_low
                                      | (fail.byz_ack_advance > 0)
                                      | fail.byz_bcast_partial
                                      | fail.byz_ack_stale)
    honest_s = (fail.crash_s < 0) & ~(fail.byz_send_drop
                                      | fail.byz_equiv_send
                                      | (fail.byz_hq_advance > 0))

    # broadcast reach matrix (n_r, n_r): who hears j's intra-RSM broadcast.
    partial_reach = idx_r[None, :] < fail.bcast_limit
    reach = jnp.where(fail.byz_bcast_partial[:, None], partial_reach,
                      jnp.ones((n_r, n_r), dtype=bool))
    reach = reach & (idx_r[None, :] != idx_r[:, None])

    def step(state: SimState, t: jnp.ndarray):
        alive_s = (fail.crash_s < 0) | (t < fail.crash_s)
        alive_r = (fail.crash_r < 0) | (t < fail.crash_r)

        # (1) broadcasts queued last round land now ------------------------
        with jax.named_scope("c3b_broadcast"):
            bcast_sent = state.bcast_q & alive_r[:, None]
            recv_from_bcast = jnp.einsum("jk,ji->ik", bcast_sent, reach) > 0
            recv_has = state.recv_has | (recv_from_bcast & alive_r[:, None])
            bcast_done = state.bcast_done | bcast_sent

        # (2) retransmission declaration + election (knowledge of t-1) -----
        with jax.named_scope("c3b_quorum"):
            quacked_msg_prev, lost_prev, qprefix_prev = stake_quorum_bitmap(
                state.known, state.repeat_c, stakes_r, fail.quack_thresh,
                fail.dup_thresh, use_pallas=spec.use_pallas_quack)
        with jax.named_scope("c3b_retransmit"):
            # losses can only be declared for messages whose original
            # dispatch already happened; under commit gating the dispatch
            # bit (not the schedule round) is what proves that.
            declared = lost_prev & state.orig_sent[None, :]
            retry_new = state.retry + declared.astype(jnp.int32)
            elected, re_hot, drop_re = _retransmit_lookups(
                spec, fail.drop_pair, abs_idx, orig_recv, retry_new)
            resend = (declared & elected & alive_s[:, None]
                      & ~fail.byz_send_drop[:, None])
            # clear complaint trackers where a loss was declared (fresh cycle)
            complaint = jnp.where(declared[:, None, :], False, state.complaint)
            repeat_c = jnp.where(declared[:, None, :], False, state.repeat_c)
            # adversary: an equivocating sender's retransmissions carry a
            # payload conflicting with the original — receivers detect the
            # mismatch and discard them wholesale (no store, no ack claim,
            # no hq metadata heard); the wire copy still happened (metrics
            # count `resend` itself) and the retry counter/rotation advance,
            # so the election keeps rotating toward an honest retransmitter.
            # Selective per-pair drops kill the copy in the network instead:
            # same observable non-delivery, but scoped to (sender, receiver).
            resend_land = resend & ~fail.byz_equiv_send[:, None] & ~drop_re

        # (3) original sends + landing --------------------------------------
        with jax.named_scope("c3b_send"):
            # a message is due once its schedule round has passed AND its
            # entry is committed on the source RSM (commit_floor gate); the
            # dispatch attempt happens exactly once (orig_sent), whether or
            # not the scheduled sender is still alive — matching the ungated
            # semantics where a crashed sender's message is simply never sent.
            due = ((orig_step <= t) & (abs_idx < fail.commit_floor)
                   & ~state.orig_sent)
            # whether each slot's original sender may send, and the
            # selective drop of the original copy: the (orig sender, orig
            # receiver) pair is dropped in the network after being sent
            s_hot, r_hot, may_send, drop_o = _send_lookups(
                alive_s & ~fail.byz_send_drop, fail.drop_pair,
                orig_sender, orig_recv)
            orig_ok = due & may_send
            orig_sent = state.orig_sent | due
            orig_land = orig_ok & ~drop_o
            s_orig = orig_land[None, :] & r_hot
        with jax.named_scope("c3b_retransmit"):
            re_land = re_hot & resend_land[:, None, :]      # (n_s, n_r, W)
            s_re = re_land.any(axis=0)
        with jax.named_scope("c3b_send"):
            wire = s_orig | s_re                                   # (n_r, W)
            land = wire & alive_r[:, None] & ~fail.byz_recv_drop[:, None]
            recv_has = recv_has | land
            bcast_q = land & ~bcast_done
            deliver_now = (recv_has & honest_r[:, None]).any(axis=0)
            deliver_time = jnp.where((state.deliver_time < 0) & deliver_now,
                                     t, state.deliver_time)

        # (3b) highest-quacked metadata rides on every landed data message:
        # a sender's current quacked prefix reaches every receiver it sent
        # anything to this round (constant-size piggyback, §4.3). Window
        # slots below `base` are all-quacked by the retirement rule, so the
        # absolute prefix is base + the in-window prefix.
        with jax.named_scope("c3b_hq"):
            qp_prev = base + qprefix_prev
            e_lk = s_hot & orig_land[None, :]                      # (n_s, W)
            sent_orig_to = jnp.einsum("lk,ik->li", e_lk.astype(jnp.int32),
                                      s_orig.astype(jnp.int32)) > 0
        with jax.named_scope("c3b_retransmit"):
            sent_re_to = re_land.any(axis=2)                       # (n_s, n_r)
        with jax.named_scope("c3b_hq"):
            heard = (sent_orig_to | sent_re_to).T                  # (n_r, n_s)
            # adversary: an hq-lying sender inflates its piggybacked prefix
            # per receiver — receiver i hears min(true + adv + i, m), so no
            # two receivers can cross-check the same claim (equivocation on
            # the §4.3 metadata). The r_s+1 attestation quorum is the
            # defence: a floor only forms where >= r_s+1 stake agrees, and
            # at most r_s of it can be lying.
            hq_lie = fail.byz_hq_advance                            # (n_s,)
            hq_claim = jnp.where(
                hq_lie[None, :] > 0,
                jnp.minimum(qp_prev[None, :] + hq_lie[None, :]
                            + idx_r[:, None], m),
                qp_prev[None, :])                               # (n_r, n_s)
            hq_new = jnp.where(heard & alive_r[:, None], hq_claim, 0)
            hq_reports = jnp.maximum(state.hq_reports,
                                     hq_new.astype(jnp.int32))

        # (4) acknowledgements ---------------------------------------------
        with jax.named_scope("c3b_ack"):
            ack_floor = weighted_quorum_prefix(hq_reports, stakes_s,
                                               fail.hq_thresh)
            ack_floor = jnp.maximum(state.ack_floor, ack_floor)
            eff = recv_has | (abs_idx[None, :] < ack_floor[:, None])
            cum, claim, _known_mask = claim_bitmask(eff, phi, base, m)
            miss = missing_below_horizon(eff, phi, base)
            # Byzantine lies --------------------------------------------------
            cum = jnp.where(fail.byz_ack_low, 0, cum)
            cum = jnp.where(fail.byz_ack_advance > 0,
                            jnp.minimum(cum + fail.byz_ack_advance, m), cum)
            claim = jnp.where(fail.byz_ack_low[:, None], False, claim)
            claim = jnp.where((fail.byz_ack_advance > 0)[:, None],
                              abs_idx[None, :] < cum[:, None], claim)
            miss = jnp.where(fail.byz_ack_low[:, None],
                             abs_idx[None, :] < phi, miss)
            miss = jnp.where((fail.byz_ack_advance > 0)[:, None], False, miss)
            # the ack rotation: receiver j acks sender (j + t) mod n_s, so
            # `upd` marks exactly the (sender, receiver) pairs whose ack
            # state refreshes this round
            tgt = (idx_r + t) % n_s                                  # (n_r,)
            upd = ((tgt[None, :] == idx_s[:, None])
                   & alive_r[None, :])                       # (n_s, n_r)
            # adversary: a stale-acking receiver replays its *previous* ack
            # to this round's target verbatim — the cum counter, prefix
            # claim and complaint list it last sent that sender (zero/empty
            # before the first ack). A replayed QUACK is truthful-but-old:
            # monotone claims can never fabricate receipt, but the frozen
            # cum counter trips the duplicate-cum complaint at the sender,
            # manufacturing loss suspicion and resend load (applied LAST so
            # a stale lie freezes whatever lie the other masks produced).
            stale = fail.byz_ack_stale                               # (n_r,)
            prev_cum = jnp.maximum(
                jnp.where(upd, state.last_cum, 0).sum(axis=0), 0)    # (n_r,)
            prev_miss = jnp.where(upd[:, :, None], state.complaint,
                                  False).any(axis=0)                 # (n_r, W)
            cum = jnp.where(stale, prev_cum, cum)
            claim = jnp.where(stale[:, None],
                              abs_idx[None, :] < prev_cum[:, None], claim)
            miss = jnp.where(stale[:, None], prev_miss, miss)
            # implicit duplicate-cum complaint: cum unchanged since last ack to
            # the same sender => complain about index cum (if it exists).
            dup_cum = (state.last_cum == cum[None, :])           # (n_s, n_r)
            dup_complaint = (dup_cum[:, :, None]
                             & (abs_idx[None, None, :] == cum[None, :, None])
                             & (cum[None, :, None] < m))
            new_complaint = miss[None, :, :] | dup_complaint   # (n_s,n_r,W)
            known = state.known | (upd[:, :, None] & claim[None, :, :])
            repeat_c = jnp.where(upd[:, :, None],
                                 repeat_c | (complaint & new_complaint),
                                 repeat_c)
            complaint = jnp.where(upd[:, :, None], new_complaint, complaint)
            last_cum = jnp.where(upd, cum[None, :], state.last_cum)

        # (5) QUACK bookkeeping --------------------------------------------
        with jax.named_scope("c3b_quorum"):
            # the lost bitmap is unused here (loss declaration works on t-1
            # knowledge, step 2), so the loss quorum is dropped at the call
            quacked_msg, _, qprefix = stake_quorum_bitmap(
                known, repeat_c, stakes_r, fail.quack_thresh,
                fail.dup_thresh, use_pallas=spec.use_pallas_quack,
                need_lost=False)
            quack_time = jnp.where((state.quack_time < 0) & quacked_msg,
                                   t, state.quack_time)

        new_state = SimState(
            recv_has=recv_has, bcast_q=bcast_q, bcast_done=bcast_done,
            orig_sent=orig_sent,
            known=known, complaint=complaint, repeat_c=repeat_c,
            last_cum=last_cum, retry=retry_new, quack_time=quack_time,
            deliver_time=deliver_time, hq_reports=hq_reports,
            ack_floor=ack_floor, base=state.base,
            retired_delivered=state.retired_delivered)

        with jax.named_scope("c3b_telemetry"):
            qp = base + qprefix
            min_qp = jnp.min(jnp.where(honest_s, qp, _BIG))
            metrics = StepMetrics(
                cross_msgs=(orig_ok.sum() + resend.sum()).astype(jnp.int32),
                intra_msgs=jnp.einsum("jk,j->", bcast_sent.astype(jnp.int32),
                                      reach.sum(axis=1).astype(jnp.int32)
                                      ).astype(jnp.int32),
                resends=resend.sum().astype(jnp.int32),
                acks=alive_r.sum().astype(jnp.int32),
                delivered=((deliver_time >= 0).sum().astype(jnp.int32)
                           + state.retired_delivered),
                min_quack_prefix=min_qp.astype(jnp.int32),
            )
        return new_state, metrics

    return step


# the window-layout invariants (_WINDOW_FILLS / _window_shapes) and the
# host<->device / width-migration helpers live in core/snapshot.py — one
# shared home for the simulator, the dense-migration path and the
# repro.replay checkpoint machinery.


def _init_state(spec: SimSpec, w: int) -> SimState:
    n_s, n_r = spec.n_s, spec.n_r
    shapes = _window_shapes(n_s, n_r, w)
    window = {
        name: jnp.full(shapes[name], fill,
                       dtype=(bool if isinstance(fill, bool) else jnp.int32))
        for name, fill in _WINDOW_FILLS.items()}
    f = jnp.zeros
    return SimState(
        **window,
        last_cum=jnp.full((n_s, n_r), -1, dtype=jnp.int32),
        hq_reports=f((n_r, n_s), dtype=jnp.int32),
        ack_floor=f((n_r,), dtype=jnp.int32),
        base=jnp.zeros((), dtype=jnp.int32),
        retired_delivered=jnp.zeros((), dtype=jnp.int32),
    )


def _sched_arrays(spec: SimSpec):
    # host (numpy) constants: a device array closed over by a jitted
    # program is fetched back to the host when the program is lowered,
    # which the debug_checks transfer guard refuses off the CPU
    return tuple(np.asarray(a, dtype=np.int32) for a in
                 (spec.orig_sender, spec.orig_recv, spec.orig_step))


def _build_run(nspec: SimSpec):
    """Dense full-stream runner: window = [0, M), no rotation.

    With ``collect_metrics`` the scan carry becomes ``(state, carry)``
    where ``carry`` is the obs fabric's :class:`MetricsCarry`; metrics
    off, the program is byte-identical to before the fabric existed
    (the wrapper is a static python branch, asserted in
    ``tests/test_obs.py``).
    """
    sched_full = _sched_arrays(nspec)
    collect = nspec.collect_metrics

    def run(fail: FailArrays):
        sched = tuple(jnp.asarray(a) for a in sched_full)
        step = _protocol_step(nspec, fail, sched, 0, nspec.m)
        state0 = _init_state(nspec, nspec.m)
        ts = jnp.arange(nspec.steps, dtype=jnp.int32)
        if not collect:
            return jax.lax.scan(step, state0, ts)

        def step_obs(carry, t):
            s, mc = carry
            s2, ms = step(s, t)
            with jax.named_scope("c3b_telemetry"):
                mc = update_metrics(mc, s, s2, ms, t)
            return (s2, mc), ms

        return jax.lax.scan(step_obs,
                            (state0, init_metrics_carry(nspec.m)), ts)

    return run


@functools.lru_cache(maxsize=64)
def _compiled_sim(nspec: SimSpec):
    return jax.jit(_build_run(nspec))


@functools.lru_cache(maxsize=64)
def _compiled_batch(nspec: SimSpec):
    return jax.jit(jax.vmap(_build_run(nspec)))


def _rotate_device(s: SimState, f, w: int) -> SimState:
    """Shift the ring buffers left by the (traced) GC frontier ``f``.

    Pure jnp — runs inside the compiled chunk. Each window-indexed array
    is extended by W fresh-fill slots and re-sliced at offset ``f``
    (``lax.dynamic_slice``), which is the in-graph form of the ring
    rotation: columns ``[f, W)`` move to ``[0, W - f)`` and the tail
    refills with fresh slots. ``base`` advances by ``f`` as traced state.
    """
    col = jnp.arange(w, dtype=jnp.int32)

    def shift(a, fill):
        ext = jnp.concatenate(
            [a, jnp.full(a.shape[:-1] + (w,), fill, dtype=a.dtype)],
            axis=-1)
        return jax.lax.dynamic_slice_in_dim(ext, f, w, axis=-1)

    retired_deliv = ((s.deliver_time >= 0) & (col < f)).sum()
    return s._replace(
        **{name: shift(getattr(s, name), fill)
           for name, fill in _WINDOW_FILLS.items()},
        base=(s.base + f).astype(jnp.int32),
        retired_delivered=(s.retired_delivered
                           + retired_deliv).astype(jnp.int32))


# number of times any windowed chunk program has been *traced* (i.e.
# staged for compilation). Warm dispatches do not bump it, so the delta
# across a replay / what-if fork batch is exactly the number of fresh
# compilations it cost — the observable behind the "reusing the already-
# compiled windowed chunk" contract (tests/test_replay.py, bench_replay).
_CHUNK_TRACES = [0]

# pipeline observability: device dispatches issued by the windowed engine
# (one fused superchunk = one dispatch, however many chunks it fuses) and
# host syncs (places the host loop blocked on device results: queue
# drains, checkpoint/migration/final state materializations). The deltas
# across a run are what bench_pipeline and the CI smoke assert on —
# counters, not wall time, so the ~K× dispatch reduction is checked
# deterministically.
_CHUNK_DISPATCHES = [0]
_HOST_SYNCS = [0]
# dispatches issued while the previous one was still undrained: how
# often the async double buffering actually launched ahead
_CHUNK_LAUNCH_AHEAD = [0]


def chunk_trace_count() -> int:
    """How many windowed chunk tracings (compilations) happened so far."""
    return _CHUNK_TRACES[0]


def chunk_dispatch_count() -> int:
    """Device dispatches issued by the windowed engine so far."""
    return _CHUNK_DISPATCHES[0]


def chunk_launch_ahead_count() -> int:
    """Dispatches issued while the previous dispatch was undrained."""
    return _CHUNK_LAUNCH_AHEAD[0]


def host_sync_count() -> int:
    """Times the windowed engine's host loop blocked on device results."""
    return _HOST_SYNCS[0]


def _donate_state() -> Tuple[int, ...]:
    """Scan-state donation: the chunk callable consumes the carried
    SimState, so its input buffers can be aliased to the outputs (no
    per-chunk O(B·W) copy, halved peak state memory). XLA implements
    input-output aliasing on TPU/GPU; the CPU client ignores donations
    (with a warning), so the hint is only attached where it does
    something. Evaluated lazily (the callers are lru-cached, so once per
    program) — probing the backend at import time would initialize JAX
    as an import side effect and freeze the decision before the user
    could configure the platform."""
    return (1,) if jax.default_backend() != "cpu" else ()


def _build_chunk(nspec: SimSpec, w_slots: int, chunk_len: int, rotate: bool):
    """Windowed chunk: ``chunk_len`` rounds + in-graph GC rotation.

    ``state.base`` is traced, so one compilation serves every window
    position (and, vmapped, every scenario's position). When ``rotate``
    the chunk computes the GC frontier in-graph, emits the pre-rotation
    outputs as a ``ChunkQueue`` and returns the rotated state; the final
    chunk of a run is instantiated with ``rotate=False`` (frontier
    trajectory matches the host-rotation semantics exactly).

    With ``collect_metrics`` the carried state is ``(SimState,
    MetricsCarry)`` and a scalar-only :class:`MetricsBlock` snapshot is
    emitted next to the queue (it rides the same drain — zero extra
    transfers); metrics off, the signature and jaxpr are byte-identical
    to the fabric never existing (static python branches only).
    """
    osend, orecv, ostep = (np.asarray(a) for a in
                           (nspec.orig_sender, nspec.orig_recv,
                            nspec.orig_step))
    # numpy, not device, constants (see _sched_arrays)
    pad = lambda a, fill: np.concatenate(
        [a, np.full(w_slots, fill, dtype=a.dtype)]).astype(np.int32)
    osend_p, orecv_p = pad(osend, 0), pad(orecv, 0)
    ostep_p = pad(np.minimum(ostep, _NEVER_STEP), _NEVER_STEP)
    collect = nspec.collect_metrics

    def chunk(fail: FailArrays, carry, t0):
        _CHUNK_TRACES[0] += 1       # body runs only while tracing
        state, mc = carry if collect else (carry, None)
        base0 = state.base
        sl = lambda a: jax.lax.dynamic_slice(a, (base0,), (w_slots,))
        sched_w = (sl(osend_p), sl(orecv_p), sl(ostep_p))
        step = _protocol_step(nspec, fail, sched_w, base0, w_slots)
        ts = t0 + jnp.arange(chunk_len, dtype=jnp.int32)
        if collect:
            def step_obs(c, t):
                s, mcc = c
                s2, ms = step(s, t)
                with jax.named_scope("c3b_telemetry"):
                    mcc = update_metrics(mcc, s, s2, ms, t)
                return (s2, mcc), ms

            (state, mc), ms = jax.lax.scan(step_obs, (state, mc), ts)
        else:
            state, ms = jax.lax.scan(step, state, ts)
        if not rotate:
            queue = ChunkQueue(state.quack_time, state.deliver_time,
                               state.retry, state.recv_has, base0,
                               jnp.zeros((), dtype=jnp.int32))
            if collect:
                with jax.named_scope("c3b_telemetry"):
                    blk = snapshot_metrics(mc)
                return (state, mc), ms, queue, blk
            return state, ms, queue
        with jax.named_scope("c3b_rotate"):
            f = gc_frontier_device(
                base=base0, t_next=t0 + chunk_len, m=nspec.m,
                known=state.known, bcast_q=state.bcast_q,
                recv_has=state.recv_has, ack_floor=state.ack_floor,
                stakes_r=fail.stakes_r, quack_thresh=fail.quack_thresh,
                orig_sent=state.orig_sent, crash_r=fail.crash_r,
                byz_ack_low=fail.byz_ack_low)
            queue = ChunkQueue(state.quack_time, state.deliver_time,
                               state.retry, state.recv_has, base0, f)
            state = _rotate_device(state, f, w_slots)
        if collect:
            with jax.named_scope("c3b_telemetry"):
                mc = rotate_metrics(mc, f, w_slots)
                blk = snapshot_metrics(mc)
            return (state, mc), ms, queue, blk
        return state, ms, queue

    return chunk


@functools.lru_cache(maxsize=64)
def _compiled_batch_chunk(nspec: SimSpec, w_slots: int, chunk_len: int,
                          rotate: bool = True):
    """Per-scenario failure masks AND window bases, one dispatch.

    Single windowed runs go through the same program as a batch of one,
    so there is exactly one chunk kernel to keep correct.
    """
    return jax.jit(jax.vmap(_build_chunk(nspec, w_slots, chunk_len, rotate),
                            in_axes=(0, 0, None)),
                   donate_argnums=_donate_state())


@functools.lru_cache(maxsize=64)
def _compiled_batch_superchunk(nspec: SimSpec, w_slots: int,
                               chunk_len: int, k: int):
    """K fused chunk bodies (rotations included) in ONE compiled dispatch.

    A ``lax.scan`` over chunk boundaries: each inner iteration runs one
    full vmapped chunk — ``chunk_len`` protocol rounds, in-graph GC
    frontier, ring rotation — and emits its pre-rotation
    :class:`ChunkQueue`; the scan stacks the K queues (and the K
    per-chunk metric blocks) into one K-deep device-side buffer the host
    drains after the dispatch returns. The chunk body is traced once
    regardless of K (the trace counter moves by 1), host round-trips
    drop by K×, and because the body is the *same* function the
    synchronous loop dispatches, a fused run is bit-identical to K
    sequential dispatches.

    The host's per-boundary adaptive-window overflow check moves
    in-graph: ``needs`` carries the precomputed dispatch horizon
    ``dispatched_by[t0 + (i+1)*chunk_len - 1]`` per inner chunk (a
    traced input — one compilation serves every span), and before inner
    chunk ``i`` runs, the *exact* device bases are tested against it.
    The moment any lane would overflow, the remaining chunk bodies are
    skipped (a ``lax.cond`` — the untaken branch costs nothing at run
    time) and the per-chunk ``ok`` flags tell the host how many chunks
    actually executed, so it rewinds to that boundary and takes the
    growth decision there with exactly the bases K = 1 would have seen.
    """
    chunk = jax.vmap(_build_chunk(nspec, w_slots, chunk_len, rotate=True),
                     in_axes=(0, 0, None))
    collect = nspec.collect_metrics

    def superchunk(fail: FailArrays, carry0, t0, needs):
        sim0 = carry0[0] if collect else carry0
        n_b = sim0.base.shape[0]
        n_s, n_r = nspec.n_s, nspec.n_r
        zero_q = ChunkQueue(
            quack_time=jnp.zeros((n_b, n_s, w_slots), jnp.int32),
            deliver_time=jnp.zeros((n_b, w_slots), jnp.int32),
            retry=jnp.zeros((n_b, n_s, w_slots), jnp.int32),
            recv_has=jnp.zeros((n_b, n_r, w_slots), bool),
            base=jnp.zeros((n_b,), jnp.int32),
            count=jnp.zeros((n_b,), jnp.int32))
        zero_ms = StepMetrics(*(jnp.zeros((n_b, chunk_len), jnp.int32)
                                for _ in StepMetrics._fields))

        def body(carry, xs):
            st, alive = carry
            i, need_i = xs
            sim = st[0] if collect else st
            # the same per-scenario rule the host loop applies at a
            # boundary: window need capped by the commit floor, measured
            # against each lane's own (exact, in-graph) base
            over = (jnp.minimum(need_i, fail.commit_floor - 1)
                    - sim.base)
            ok = jnp.logical_and(alive, (over < w_slots).all())
            if collect:
                # skipped chunks re-emit the carried accumulator
                # snapshot so the stacked blocks stay structurally
                # K-deep; the host ignores them via ``oks``
                st, ms, queue, blk = jax.lax.cond(
                    ok,
                    lambda s: chunk(fail, s, t0 + i * chunk_len),
                    lambda s: (s, zero_ms,
                               zero_q._replace(base=s[0].base),
                               snapshot_metrics(s[1])),
                    st)
                return (st, ok), (ms, queue, ok, blk)
            st, ms, queue = jax.lax.cond(
                ok,
                lambda s: chunk(fail, s, t0 + i * chunk_len),
                lambda s: (s, zero_ms,
                           zero_q._replace(base=s.base)),
                st)
            return (st, ok), (ms, queue, ok)

        if collect:
            (carry0, _), (ms, queues, oks, blks) = jax.lax.scan(
                body, (carry0, jnp.bool_(True)),
                (jnp.arange(k, dtype=jnp.int32), needs))
            return carry0, ms, queues, oks, blks
        (carry0, _), (ms, queues, oks) = jax.lax.scan(
            body, (carry0, jnp.bool_(True)),
            (jnp.arange(k, dtype=jnp.int32), needs))
        return carry0, ms, queues, oks

    return jax.jit(superchunk, donate_argnums=_donate_state())


def superchunk_program(spec: SimSpec, lanes: int = 1):
    """The fused superchunk program the engine dispatches for ``lanes``
    copies of windowed ``spec`` at its initial window, with abstract
    arguments of the shapes it is called with. ``program.lower(*args)``
    stages exactly what a run compiles, without running anything — for
    compile-only checks and for reading the compiled program's text."""
    nspec = _neutral(spec)
    w = spec.window_slots
    c, k = max(spec.chunk_steps, 1), max(spec.superchunk, 1)
    program = _compiled_batch_superchunk(
        dataclasses.replace(nspec, steps=0), w, c, k)

    def init():
        carry = _init_state(nspec, w)
        if spec.collect_metrics:
            carry = (carry, init_metrics_carry(w))
        return _fail_arrays(spec), carry

    lane = lambda a: jax.ShapeDtypeStruct((lanes,) + a.shape, a.dtype)
    fails, carry = jax.tree_util.tree_map(lane, jax.eval_shape(init))
    return program, (fails, carry, jax.ShapeDtypeStruct((), jnp.int32),
                     jax.ShapeDtypeStruct((k,), jnp.int32))


# host materialization / width migration are the shared snapshot
# utilities; thin aliases keep the simulator's internal vocabulary.
_np_state = host_state
_grow_state = pad_window


def _widen_on_overflow(spec: SimSpec, w: int, base: int, need: int,
                       t: int) -> Optional[int]:
    """Overflow policy: raise (strict), grow 2x, or None => dense layout.

    ``None`` tells the caller to migrate the windowed scan state into the
    dense layout (base 0, W = M) and continue — no rerun from scratch.
    """
    if not spec.adaptive_window:
        raise ValueError(
            f"sliding window overflow: round {t} dispatches message "
            f"{need} but the window covers [{base}, {base + w}) — the GC "
            f"frontier is {base}. Increase SimConfig.window_slots (or use "
            f"window_slots='auto'), or leave adaptive_window=True for "
            f"automatic growth / dense-layout migration.")
    return grow_window(w, base, need, spec.m)


def _migrate_dense_batch(spec: SimSpec, state: SimState,
                         bases: np.ndarray, out_quack: np.ndarray,
                         out_deliver: np.ndarray, out_retry: np.ndarray,
                         out_recv: np.ndarray) -> SimState:
    """Embed the windowed scan state into the dense layout (base 0, W=M).

    Adaptive-growth endpoint: when the next doubling would reach the full
    stream length, the run keeps its partial progress instead of rerunning
    on the dense kernel from round 0. Live window columns land at their
    absolute positions ``[base_b, base_b + W)``; columns below each
    scenario's base are reconstructed from the already-drained retired
    outputs plus the retirement invariants themselves — a retired slot is
    QUACKed at *every* sender (``known`` may be set all-True without
    changing any threshold decision), effectively received at every
    receiver that still matters (``recv_has`` restored from the drained
    snapshot; the rest is covered by the preserved ack floor), has no
    broadcast pending and its original send dispatched. Per-replica state
    (``last_cum``/``hq_reports``/``ack_floor``) carries over unchanged, so
    the continued run is bit-identical in every observable output to a
    dense run from round 0 (``tests/test_windowed.py``).

    One-off host-side transform (numpy in, device out) — the steady-state
    chunk loop still never round-trips the scan state.
    """
    n_b = len(bases)
    n_s, n_r, m = spec.n_s, spec.n_r, spec.m
    state = _np_state(state)
    w = state.deliver_time.shape[-1]
    shapes = _window_shapes(n_s, n_r, m)
    dense = {
        name: np.full((n_b,) + shapes[name], fill,
                      dtype=(bool if isinstance(fill, bool) else np.int32))
        for name, fill in _WINDOW_FILLS.items()}
    for b in range(n_b):
        lo = int(bases[b])
        live = min(w, m - lo)
        if live > 0:
            for name in _WINDOW_FILLS:
                dense[name][b][..., lo:lo + live] = \
                    getattr(state, name)[b][..., :live]
        if lo > 0:
            dense["recv_has"][b][..., :lo] = out_recv[b][..., :lo]
            dense["retry"][b][..., :lo] = out_retry[b][..., :lo]
            dense["quack_time"][b][..., :lo] = out_quack[b][..., :lo]
            dense["deliver_time"][b][:lo] = out_deliver[b][:lo]
            dense["known"][b][..., :lo] = True
            dense["bcast_done"][b][..., :lo] = True
            dense["orig_sent"][b][:lo] = True
    return SimState(
        **{name: jnp.asarray(a) for name, a in dense.items()},
        last_cum=jnp.asarray(state.last_cum),
        hq_reports=jnp.asarray(state.hq_reports),
        ack_floor=jnp.asarray(state.ack_floor),
        base=jnp.zeros((n_b,), dtype=jnp.int32),
        retired_delivered=jnp.zeros((n_b,), dtype=jnp.int32),
    )


def _max_msg_by_round(spec: SimSpec) -> np.ndarray:
    """r[t] = highest message index dispatched at or before round t."""
    ostep = np.asarray(spec.orig_step, dtype=np.int64)
    r = np.full(max(spec.steps, 1), -1, dtype=np.int64)
    valid = ostep < spec.steps
    np.maximum.at(r, ostep[valid], np.nonzero(valid)[0])
    return np.maximum.accumulate(r)


def _run_windowed(spec: SimSpec) -> SimResult:
    """Single windowed run == a batch of one (same kernel, same drains)."""
    return _run_windowed_batch([spec])[0]


def _dense_send_step(spec: SimSpec) -> np.ndarray:
    """Dispatch rounds of the dense (ungated) path: the schedule round,
    -1 for messages whose round never arrives within ``steps``."""
    ostep = np.asarray(spec.orig_step, dtype=np.int64)
    return np.where(ostep < spec.steps, ostep, -1).astype(np.int32)


def _latency_from(send_step: np.ndarray,
                  deliver_time: np.ndarray) -> np.ndarray:
    """Per-message retire-step - send-step; -1 = not delivered."""
    return np.where(deliver_time >= 0, deliver_time - send_step,
                    -1).astype(np.int32)


def run_simulation(spec: SimSpec) -> SimResult:
    """Run one spec: windowed when ``spec.window_slots > 0``, else dense."""
    if spec.window_slots:
        return _run_windowed(spec)
    carry, ms = _compiled_sim(_neutral(spec))(_fail_arrays(spec))
    # one explicit batched fetch — per-leaf np.asarray here is an
    # implicit d2h transfer the analysis sanitizer rejects
    carry, ms = jax.device_get((carry, ms))
    final, mc = carry if spec.collect_metrics else (carry, None)
    ss = _dense_send_step(spec)
    return SimResult(
        spec=spec,
        metrics=StepMetrics(*ms),
        quack_time=final.quack_time,
        deliver_time=final.deliver_time,
        retry=final.retry,
        recv_has=final.recv_has,
        gc_frontiers=np.zeros(1, dtype=np.int64),
        final_window_slots=spec.m,
        send_step=ss,
        delivery_latency=_latency_from(ss, final.deliver_time),
        obs=obs_from_carry(mc) if mc is not None else None,
    )


def retire_safety_stakes_ok(spec: SimSpec) -> bool:
    """Whether the GC retire-implies-delivered invariant is provable.

    A retired slot is QUACKed at every sender, and a QUACK quorum
    (``quack_thresh`` = u_r+1 stake) intersects at least one *honest*
    receiver's truthful claim — unless receivers that can fabricate
    claims (``byz_ack_advance``) control a whole quorum by themselves,
    or senders lying in the §4.3 hq piggyback (``byz_hq_advance``)
    control a whole attestation quorum (``hq_thresh`` = r_s+1) and can
    raise ack floors past undelivered messages. Within those stake
    budgets the invariant is exact (the engine's debug retire check and
    ``repro.adversary.safety`` assert it); beyond them the protocol's
    own assumptions are violated and retirement may outrun delivery.
    Every other adversary kind (drops, equivocation, stale replays,
    low acks, partial broadcasts) only ever *suppresses* claims, so it
    can never make the invariant unsound.
    """
    st_r = np.asarray(spec.stakes_r, dtype=np.float64)
    adv = np.asarray(spec.byz_ack_advance, dtype=np.int64)
    fabricating = float(st_r[adv > 0].sum())
    if fabricating >= float(spec.quack_thresh):
        return False
    if spec.byz_hq_advance is not None:
        st_s = np.asarray(spec.stakes_s, dtype=np.float64)
        hq = np.asarray(spec.byz_hq_advance, dtype=np.int64)
        if float(st_s[hq > 0].sum()) >= float(spec.hq_thresh):
            return False
    return True


def _stacked_fails(specs: Sequence[SimSpec]) -> FailArrays:
    fails = [_fail_arrays(s) for s in specs]
    return FailArrays(*(jnp.stack([getattr(f, name) for f in fails])
                        for name in FailArrays._fields))


def _run_dense_batch(specs: List[SimSpec]) -> List[SimResult]:
    nspec = _neutral(specs[0])
    carry, ms = _compiled_batch(nspec)(_stacked_fails(specs))
    carry, ms = jax.device_get((carry, ms))
    collect = specs[0].collect_metrics
    finals, mc = carry if collect else (carry, None)
    out = []
    for b, spec in enumerate(specs):
        ss = _dense_send_step(spec)
        out.append(SimResult(
            spec=spec,
            metrics=StepMetrics(*(x[b] for x in ms)),
            quack_time=finals.quack_time[b],
            deliver_time=finals.deliver_time[b],
            retry=finals.retry[b],
            recv_has=finals.recv_has[b],
            gc_frontiers=np.zeros(1, dtype=np.int64),
            final_window_slots=spec.m,
            send_step=ss,
            delivery_latency=_latency_from(ss, finals.deliver_time[b]),
            obs=obs_from_final(mc, [], b) if collect else None,
        ))
    return out


def _scatter_retired(bases: np.ndarray, counts: np.ndarray, srcs,
                     outs) -> np.ndarray:
    """Fold one drained queue block into the (B, ..., M) output mirrors.

    Writes each lane's leading ``counts[b]`` window columns to absolute
    slots ``[bases[b], bases[b] + counts[b])`` — one vectorized
    advanced-indexing write per output array instead of a per-lane
    Python copy loop. ``srcs``/``outs`` are the (quack_time,
    deliver_time, retry, recv_has) quadruples. Returns the advanced
    per-lane bases (the inputs are never mutated).
    """
    qq, qd, qr, qh = srcs
    out_quack, out_deliver, out_retry, out_recv = outs
    counts = np.asarray(counts, dtype=np.int64)
    if counts.any():
        w = qd.shape[-1]
        mask = np.arange(w, dtype=np.int64)[None, :] < counts[:, None]
        rows, cols = np.nonzero(mask)
        abs_cols = bases[rows] + cols
        out_quack[rows, :, abs_cols] = qq[rows, :, cols]
        out_deliver[rows, abs_cols] = qd[rows, cols]
        out_retry[rows, :, abs_cols] = qr[rows, :, cols]
        out_recv[rows, :, abs_cols] = qh[rows, :, cols]
    return bases + counts


def _concat_metrics(n_b: int, metric_parts) -> StepMetrics:
    """Concatenate per-chunk (B, c) metric parts into (B, t) arrays."""
    if not metric_parts:
        return StepMetrics(*(np.zeros((n_b, 0), dtype=np.int32)
                             for _ in StepMetrics._fields))
    return StepMetrics(*(
        np.concatenate([np.asarray(getattr(p, name)) for p in metric_parts],
                       axis=-1)
        for name in StepMetrics._fields))


def _run_windowed_batch(specs: List[SimSpec], commit_floors=None, *,
                        fail_schedule=None, recorder=None,
                        resume: Optional[ChunkCheckpoint] = None,
                        drain_sink=None,
                        ) -> List[SimResult]:
    """Windowed batch entry point; see ``_run_windowed_batch_impl``.

    When ``SimConfig.debug_checks`` is set the whole run executes under
    the analysis sanitizer's :func:`repro.analysis.engine_guard`: any
    implicit device->host materialization in the drain / checkpoint /
    final-flush path (a ``np.asarray`` on a ``jax.Array`` outside
    ``jax.device_get``) raises ``SanitizerError`` instead of silently
    serializing the pipeline.
    """
    _tr = obs_begin("run")
    try:
        if specs and specs[0].debug_checks:
            from ..analysis.sanitizer import engine_guard
            with engine_guard():
                return _run_windowed_batch_impl(
                    specs, commit_floors, fail_schedule=fail_schedule,
                    recorder=recorder, resume=resume,
                    drain_sink=drain_sink)
        return _run_windowed_batch_impl(
            specs, commit_floors, fail_schedule=fail_schedule,
            recorder=recorder, resume=resume, drain_sink=drain_sink)
    finally:
        obs_end(_tr, "run", cat="engine", lanes=len(specs),
                steps=specs[0].steps if specs else 0)


def _run_windowed_batch_impl(specs: List[SimSpec], commit_floors=None, *,
                             fail_schedule=None, recorder=None,
                             resume: Optional[ChunkCheckpoint] = None,
                             drain_sink=None,
                             ) -> List[SimResult]:
    """Batched windowed sweep: per-scenario failure masks AND window bases.

    The vmapped chunk rotates each scenario's ring buffers at its own GC
    frontier in-graph, so the whole sweep is one compilation with
    O(B * W) state — windowed and batched at once. Window overflow
    (checked per scenario against its own base and commit floor) grows W
    for the whole batch; when the required width would reach M the scan
    state migrates into the dense layout (``_migrate_dense_batch``) and
    the same chunk loop continues — partial progress is kept, never
    rerun. Every growth decision is recorded
    (``SimResult.window_growth_events``) with the lane that forced it
    and the overflow round, instead of the batch silently growing W.

    Execution is **pipelined** (``SimSpec.superchunk`` = K): up to K
    full rotating chunk bodies fuse into one compiled dispatch
    (``_compiled_batch_superchunk`` — a ``lax.scan`` over chunk
    boundaries with a K-deep output queue), and the host drains a
    dispatch's queue *while the next dispatch computes* (JAX async
    dispatch; at most one dispatch is ever in flight undrained). Fusion
    and the drain overlap both break automatically at every boundary
    where host interaction is mandatory — recorder checkpoints,
    ``fail_schedule`` swaps, ``commit_floors`` updates, window
    growth/dense fallback, and the final unrotated chunk — and the
    launch-ahead path is only taken when the conservative overflow bound
    (host-side ``dispatched_by``/``floors`` mirrors against the
    pre-drain bases) proves no growth decision could trigger, so every K
    is bit-identical to the K = 1 synchronous loop in outputs, metrics,
    frontier trajectories, growth events and recorded traces.
    ``chunk_dispatch_count`` / ``host_sync_count`` expose the ~K×
    dispatch and sync reduction deterministically (``bench_pipeline``),
    and ``chunk_launch_ahead_count`` how often a dispatch launched
    ahead of the previous one's drain.

    ``commit_floors``, when given, is called as ``commit_floors(t, bases)``
    before the chunk starting at round ``t`` (``bases`` = each scenario's
    current retired prefix) and must return the per-scenario commit
    floors for that chunk. The topology engine uses it to route one
    link's retired/delivered prefix into the commit stream of chained
    downstream links — the floors are traced inputs, so updating them
    between chunks costs no recompilation.

    ``fail_schedule``, when given, is called as ``fail_schedule(t)`` at
    the top of each chunk; returning a list of specs (same structure as
    ``specs``, differing only in failure masks) swaps the stacked
    ``FailArrays`` in force from round ``t`` onward — a mid-stream
    crash/heal/drop-schedule edit. The masks are traced inputs, so a
    swap costs no recompilation; returning ``None`` keeps the masks.

    ``recorder`` (an object with ``wants(t) -> bool`` and
    ``capture(ChunkCheckpoint)``) captures chunk-boundary checkpoints;
    ``resume`` restarts the loop from a previously captured checkpoint —
    the replay subsystem's entry points (``repro.replay``).

    ``drain_sink`` switches the loop into **horizon mode** (the
    ``repro.stream`` session driver): M is treated as a message horizon
    rather than an allocation. No (B, ..., M) output mirrors are built —
    every drained chunk is retired *online* into the sink
    (``sink.on_chunk(t_end, metrics, queue, block, bases)`` per inner
    chunk, ``sink.on_final(state, metrics_carry, bases, w, events, t)``
    after the terminal flush) and the call returns ``[]`` instead of
    per-lane ``SimResult``\\ s. Host memory per dispatch is O(B * W);
    the dispatch/fusion structure is byte-identical to batch mode (the
    sink rides the drains that already happen), so the zero-extra-
    dispatch contract is held by construction. Requires
    ``collect_metrics`` (the blocks *are* the live feed) and is mutually
    exclusive with ``recorder``/``resume`` (checkpoints capture O(M)
    mirrors that horizon mode never materializes); window growth stays
    available but the dense-layout fallback (O(M) state) raises instead
    of silently allocating the horizon.
    """
    _tpro = obs_begin("prologue")
    spec0 = specs[0]
    n_b = len(specs)
    nspec = _neutral(spec0)
    cspec = dataclasses.replace(nspec, steps=0)
    n_s, n_r, m = spec0.n_s, spec0.n_r, spec0.m
    c_full = max(spec0.chunk_steps, 1)

    if drain_sink is not None:
        if recorder is not None or resume is not None:
            raise ValueError("drain_sink (horizon mode) is incompatible "
                             "with recorder/resume: checkpoints capture "
                             "the O(M) output mirrors horizon mode "
                             "exists to avoid")
        if not spec0.collect_metrics:
            raise ValueError("drain_sink requires collect_metrics=True: "
                             "the MetricsBlock snapshots riding the "
                             "drain are the live telemetry feed")

    # Per-run program lookup: the lru_cached constructors hash the whole
    # frozen spec — including O(M) schedule tuples — on every call,
    # which horizon-scale runs (M ~ 1e6, thousands of dispatches) cannot
    # afford. Key by the only fields that vary inside one run.
    progs: dict = {}

    def chunk_prog(w_slots: int, c_len: int, rotate: bool):
        key = (w_slots, c_len, rotate, 1)
        fn = progs.get(key)
        if fn is None:
            fn = progs[key] = _compiled_batch_chunk(cspec, w_slots,
                                                    c_len, rotate)
        return fn

    def super_prog(w_slots: int, c_len: int, k: int):
        key = (w_slots, c_len, True, k)
        fn = progs.get(key)
        if fn is None:
            fn = progs[key] = _compiled_batch_superchunk(cspec, w_slots,
                                                         c_len, k)
        return fn

    dispatched_by = _max_msg_by_round(spec0)
    collect = spec0.collect_metrics
    ostep = np.asarray(spec0.orig_step, dtype=np.int64)

    # carry = SimState when metrics are off, (SimState, MetricsCarry)
    # when on — the two accessors keep the loop body branch-free
    _sim = (lambda cy: cy[0]) if collect else (lambda cy: cy)

    retain = drain_sink is None       # batch mode: O(M) host mirrors
    if resume is None:
        w = spec0.window_slots
        fails = _stacked_fails(specs)
        if retain:
            out_quack = np.full((n_b, n_s, m), -1, dtype=np.int32)
            out_deliver = np.full((n_b, m), -1, dtype=np.int32)
            out_retry = np.zeros((n_b, n_s, m), dtype=np.int32)
            out_recv = np.zeros((n_b, n_r, m), dtype=bool)
        else:
            out_quack = out_deliver = out_retry = out_recv = None
        carry = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_b,) + x.shape),
            _init_state(nspec, w))
        if collect:
            carry = (carry, jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n_b,) + x.shape),
                init_metrics_carry(w)))
        bases = np.zeros(n_b, dtype=np.int64)
        bases_hist = [bases.copy()]
        floors = np.full(n_b, m, dtype=np.int64)
        t = 0
        metric_parts = []
        growth_events: List[WindowGrowthEvent] = []
        # per-message dispatch-round mirror (commit-floor aware): filled
        # as floors open, feeds SimResult.delivery_latency + checkpoints
        # (horizon mode drops it — another O(M) buffer)
        send_step = (np.full((n_b, m), -1, dtype=np.int64)
                     if retain else None)
        open_floor = np.zeros(n_b, dtype=np.int64)
    else:
        if len(resume.bases) != n_b:
            raise ValueError(
                f"resume checkpoint has {len(resume.bases)} lanes, specs "
                f"describe {n_b}")
        w = int(resume.window_slots)
        fails = FailArrays(*(jnp.asarray(x) for x in resume.fails))
        out_quack = np.array(resume.out_quack, dtype=np.int32)
        out_deliver = np.array(resume.out_deliver, dtype=np.int32)
        out_retry = np.array(resume.out_retry, dtype=np.int32)
        out_recv = np.array(resume.out_recv, dtype=bool)
        carry = device_state(resume.state)
        bases = np.array(resume.bases, dtype=np.int64)
        bases_hist = [np.array(r, dtype=np.int64)
                      for r in resume.bases_hist]
        floors = np.array(resume.floors, dtype=np.int64)
        t = int(resume.t)
        metric_parts = [p for p in resume.metric_parts
                        if np.asarray(p.acks).shape[-1]]
        growth_events = list(resume.growth_events)
        if resume.send_step is not None:
            send_step = np.array(resume.send_step, dtype=np.int64)
        else:
            # pre-send_step trace: every message below the checkpoint's
            # floor dispatched at its schedule round (exact for
            # standalone links, where the floor opened at t=0)
            send_step = np.where(
                np.arange(m, dtype=np.int64)[None, :] < floors[:, None],
                ostep[None, :], -1)
        open_floor = floors.copy()
        if collect:
            carry = (carry,
                     resume_metrics_carry(w, bases, send_step, m))

    K = max(spec0.superchunk, 1)
    debug = spec0.debug_checks
    # lanes whose adversary stakes stay inside the quorum budgets have a
    # provable retire-implies-delivered invariant; the debug drain check
    # asserts it per retired slot (repro.adversary safety contract)
    retire_check = np.array([retire_safety_stakes_ok(s) for s in specs])

    pending: List[dict] = []   # dispatched, not yet drained (≤ 1 entry)
    obs_parts: List = []       # drained per-chunk MetricsBlock snapshots

    def drain_one(ent: dict) -> None:
        """Materialize one dispatch's K-deep queue + metric blocks and
        fold them into the host mirrors, inner chunk by inner chunk —
        bit-identical to K separate synchronous drains. A fused span the
        in-graph overflow guard cut short rewinds ``t`` to the boundary
        of the first unexecuted chunk; the loop re-enters there and
        takes the growth decision exactly where K = 1 would have."""
        nonlocal bases, t
        _tw = obs_begin("drain_wait")
        # one batched fetch per dispatch — the metrics blocks (when
        # collecting) ride the same device_get, zero extra transfers
        ms, queue, oks, blk = jax.device_get(
            (ent["ms"], ent["queue"], ent["oks"], ent["blk"]))
        # a successor dispatch still in flight means this wait ran
        # concurrently with device compute (PR 5 double buffering)
        obs_end(_tw, "drain_wait", cat="drain", k=ent["k"],
                overlapped=bool(pending))
        _tu = obs_begin("unpack")
        _HOST_SYNCS[0] += 1
        k = ent["k"]
        executed = k if oks is None else int(np.asarray(oks).sum())
        if executed < k:
            t = ent["t0"] + executed * ent["c"]
        for i in range(executed):
            if k == 1:
                msp, qp, bp = ms, queue, blk
            else:
                msp = StepMetrics(*(getattr(ms, name)[i]
                                    for name in StepMetrics._fields))
                qp = ChunkQueue(*(getattr(queue, name)[i]
                                  for name in ChunkQueue._fields))
                bp = None if blk is None else MetricsBlock(
                    *(getattr(blk, name)[i]
                      for name in MetricsBlock._fields))
            msp = StepMetrics(*(np.asarray(x) for x in msp))
            if retain:
                metric_parts.append(msp)
                if bp is not None:
                    obs_parts.append(bp)
            if not ent["rotate"]:
                if not retain:
                    to_sink(ent["t0"] + (i + 1) * ent["c"], msp, qp, bp)
                continue               # final chunk: nothing retired
            # the host's base mirror must track the in-graph rotation
            # exactly; the comparison is debug-gated so steady-state
            # drains never block on a consistency assertion
            if debug and not (np.asarray(qp.base) == bases).all():
                raise RuntimeError(
                    "window base mirror diverged from device rotation")
            # GC safety under adversaries: a retired slot must be
            # physically held by >= 1 replica of the receiver RSM —
            # recv_has is ground-truth receipt, so only a quorum of
            # *fabricated* claims can quack an unreceived message, and
            # that is provably impossible while fabricating stake stays
            # inside the quorum budgets (retire_safety_stakes_ok).
            # Debug-gated like the base check; repro.adversary's
            # property tests run with it.
            if debug and retire_check.any():
                cnt = np.asarray(qp.count, dtype=np.int64)
                held = np.asarray(qp.recv_has).any(axis=1)   # (B, W)
                ret = (np.arange(held.shape[-1])[None, :] < cnt[:, None])
                bad = ret & ~held & retire_check[:, None]
                if bad.any():
                    b, kk = np.argwhere(bad)[0]
                    raise RuntimeError(
                        f"GC safety violation: lane {b} retired window "
                        f"slot {kk} (abs seqno {int(bases[b]) + int(kk)}) "
                        f"that no replica has received — the frontier "
                        f"outran an undelivered message under an "
                        f"adversary whose stake budget should make that "
                        f"impossible")
            if retain:
                bases = _scatter_retired(
                    bases, qp.count,
                    (np.asarray(qp.quack_time),
                     np.asarray(qp.deliver_time),
                     np.asarray(qp.retry), np.asarray(qp.recv_has)),
                    (out_quack, out_deliver, out_retry, out_recv))
                bases_hist.append(bases.copy())
            else:
                # horizon mode: the chunk's outputs retire into the
                # sink instead of (B, ..., M) mirrors — O(B * W) per
                # drain, independent of how far the stream has run
                bases = bases + np.asarray(qp.count, dtype=np.int64)
                to_sink(ent["t0"] + (i + 1) * ent["c"], msp, qp, bp)
        obs_end(_tu, "unpack", cat="drain", k=k, executed=executed)

    def to_sink(t_end: int, msp, qp, bp) -> None:
        _ts = obs_begin("sink_chunk")
        drain_sink.on_chunk(t_end, msp, qp, bp, bases.copy())
        obs_end(_ts, "sink_chunk", cat="drain", t=t_end)

    def why_sync(async_ok: bool, last: bool, t_next: int) -> Optional[str]:
        """Why a dispatch is drained before the next one launches (None:
        it may stay in flight)."""
        if K == 1:
            return "k1"
        if not async_ok:
            return "bound"
        if last:
            return "last"
        if commit_floors is not None or (recorder is not None
                                         and recorder.wants(t_next)):
            return "host_boundary"
        return None

    def drain_all() -> None:
        while pending:
            drain_one(pending.pop(0))

    obs_end(_tpro, "prologue", cat="engine")
    while t < spec0.steps:
        _tpl = obs_begin("plan")
        c = min(c_full, spec0.steps - t)
        # (a) failure-schedule swap: host-only work — the masks are
        # traced inputs, so a swap needs no device sync
        new_specs = None if fail_schedule is None else fail_schedule(t)
        if new_specs is not None:
            new_specs = list(new_specs)
            if (len(new_specs) != n_b
                    or any(_neutral(s) != nspec for s in new_specs)):
                raise ValueError(
                    "fail_schedule must return one spec per lane, "
                    "differing from the originals only in failure "
                    "masks, stakes or quorum thresholds (all traced "
                    "inputs — anything else would force a recompile)")
            fails = _stacked_fails(new_specs)._replace(
                commit_floor=jnp.asarray(floors, dtype=jnp.int32))
            retire_check = np.array([retire_safety_stakes_ok(s)
                                     for s in new_specs])
        # (b) recorder checkpoint: mandatory host interaction — flush
        # the pipeline so the captured state is exactly the boundary
        # state and the recorded trace stays bit-exact
        if recorder is not None and recorder.wants(t):
            drain_all()
            _HOST_SYNCS[0] += 1
            _tc = obs_begin("checkpoint")
            recorder.capture(ChunkCheckpoint(
                t=t, window_slots=w, bases=bases.copy(),
                state=_np_state(_sim(carry)), fails=_np_state(fails),
                floors=floors.copy(),
                out_quack=out_quack.copy(), out_deliver=out_deliver.copy(),
                out_retry=out_retry.copy(), out_recv=out_recv.copy(),
                metric_parts=tuple(metric_parts),
                bases_hist=np.stack(bases_hist),
                growth_events=tuple(growth_events),
                send_step=send_step.copy()))
            obs_end(_tc, "checkpoint", cat="snapshot", t=t)
        # (c) commit floors are a function of this boundary's actual
        # retired prefixes, so the pipeline drains before asking
        if commit_floors is not None:
            drain_all()
            _tp = obs_begin("plan_floors")
            new_floors = np.asarray(commit_floors(t, bases.copy()),
                                    dtype=np.int64)
            obs_end(_tp, "plan_floors", cat="plan", t=t)
            if not np.array_equal(new_floors, floors):
                floors = new_floors
                fails = fails._replace(
                    commit_floor=jnp.asarray(floors, dtype=jnp.int32))
        # (c2) dispatch-round mirror: floors that opened since the last
        # boundary dispatch their newly-committed messages at
        # max(schedule round, now) — standalone links (floor = M at
        # t = 0) reduce to the schedule rounds exactly
        if send_step is not None and (floors > open_floor).any():
            for b in np.nonzero(floors > open_floor)[0]:
                ks = np.arange(open_floor[b], floors[b])
                send_step[b, ks] = np.maximum(ostep[ks], t)
                open_floor[b] = floors[b]
        # (d) per-scenario overflow check: a scenario dispatches nothing
        # past its commit floor, so its window need is capped by
        # floor - 1 and measured against its OWN base (a chained link's
        # lagging base must not force growth for messages it cannot send
        # yet). The check is evaluated against the host-side
        # dispatched_by/floors mirrors first; only a *potential*
        # overflow blocks on the in-flight dispatch for the exact bases.
        need_b = np.minimum(int(dispatched_by[t + c - 1]), floors - 1)
        if pending and (need_b - bases >= w).any():
            drain_all()
        over = need_b - bases
        b_worst = int(over.argmax())
        if over[b_worst] >= w:
            drain_all()
            new_w = _widen_on_overflow(spec0, w, int(bases[b_worst]),
                                       int(need_b[b_worst]), t + c - 1)
            growth_events.append(WindowGrowthEvent(
                step=t + c - 1, scenario=b_worst,
                need=int(need_b[b_worst]), old_w=w,
                new_w=m if new_w is None else new_w,
                dense_migration=new_w is None))
            if new_w is None:
                if not retain:
                    # the width that would have held this overflow:
                    # enough slots above the stalled lane's frontier to
                    # cover its dispatch head, rounded to the 64-slot
                    # granularity stream_window_slots uses
                    span = int(need_b[b_worst]) + 1 - int(bases[b_worst])
                    suggest = int(-(-span // 64) * 64)
                    raise RuntimeError(
                        "stream session window overflow: the dense "
                        "fallback would allocate the full horizon "
                        f"(W={w} -> M={m}). Lane {b_worst}'s dispatch "
                        f"head is {int(need_b[b_worst])} with GC "
                        f"frontier {int(bases[b_worst])}, so "
                        f"stream_window_slots >= {suggest} would have "
                        "sufficed — pass SimConfig(window_slots="
                        f"{suggest}) (or raise the slack in repro."
                        "stream.workload.stream_window_slots), or "
                        "lower the arrival rate")
                _tg = obs_begin("dense_migration")
                sim_state = _migrate_dense_batch(
                    spec0, _sim(carry), bases, out_quack,
                    out_deliver, out_retry, out_recv)
                if collect:
                    carry = (sim_state, migrate_dense_metrics(
                        carry[1], bases, send_step, m))
                else:
                    carry = sim_state
                _HOST_SYNCS[0] += 1
                bases[:] = 0
                w = m
                obs_end(_tg, "dense_migration", cat="window", t=t,
                        new_w=m)
            else:
                _tg = obs_begin("window_growth")
                if collect:
                    carry = (_grow_state(carry[0], new_w),
                             pad_metrics(carry[1], new_w))
                else:
                    carry = _grow_state(carry, new_w)
                w = new_w
                obs_end(_tg, "window_growth", cat="window", t=t,
                        new_w=new_w)
        # (e) fusion span: up to K full rotating chunks per dispatch,
        # broken at every boundary where host interaction is mandatory —
        # a recorder checkpoint, a failure-schedule swap, a commit-floor
        # update, or the final (unrotated) chunk. Window overflow inside
        # the span is guarded *in-graph* (the superchunk stops at the
        # first boundary any lane would overflow and reports how far it
        # got), so the fusion length never depends on device results.
        # the replay subsystem stays on K = 1 chunk programs end to end:
        # recorded (parent) runs execute chunk-at-a-time so they compile
        # exactly the programs every later resume / schedule-edited
        # replay reuses — fusing either side would mint per-span-length
        # programs and break the replay/fork zero-recompilation
        # contract for some checkpoint spacings (tests/test_replay.py);
        # async drains still apply.
        fusible = (resume is None and fail_schedule is None
                   and recorder is None)
        last = t + c >= spec0.steps
        k = 1
        if not last and c == c_full and commit_floors is None and fusible:
            k = min(K, (spec0.steps - t - 1) // c_full)
        # launch-ahead is safe only when the conservative bound — zero
        # frontier advance over the whole span, measured from the
        # (possibly pre-drain) host bases — proves the in-graph overflow
        # guard cannot fire, so this span is final and the next
        # boundary's planning needs nothing from this dispatch's results
        span_need = np.minimum(int(dispatched_by[t + k * c - 1]),
                               floors - 1)
        async_ok = K > 1 and bool((span_need - bases < w).all())
        if _tpl is not None:
            obs_end(_tpl, "plan", cat="plan", t=t, k=k, async_ok=async_ok,
                    why_sync=why_sync(async_ok, last, t + k * c))
        # (f) dispatch, then drain the *previous* dispatch's queue while
        # this one computes (async double buffering; JAX dispatch is
        # asynchronous, so the call returns before the device finishes)
        _td = obs_begin("dispatch")
        traces_before = _CHUNK_TRACES[0]
        blk = None
        if k == 1:
            res = chunk_prog(w, c, not last)(fails, carry, jnp.int32(t))
            if collect:
                carry, ms, queue, blk = res
            else:
                carry, ms, queue = res
            oks = None
        else:
            needs = np.asarray(dispatched_by[t + c - 1:t + k * c:c],
                               dtype=np.int32)
            res = super_prog(w, c, k)(fails, carry, jnp.int32(t),
                                      jnp.asarray(needs))
            if collect:
                carry, ms, queue, oks, blk = res
            else:
                carry, ms, queue, oks = res
        _CHUNK_DISPATCHES[0] += 1
        if pending:
            _CHUNK_LAUNCH_AHEAD[0] += 1
        obs_end(_td,
                "compile" if _CHUNK_TRACES[0] > traces_before
                else "dispatch",
                cat="dispatch", t=t, k=k)
        pending.append(dict(t0=t, k=k, c=c, rotate=not last, ms=ms,
                            queue=queue, oks=oks, blk=blk))
        t += k * c
        while len(pending) > 1:
            drain_one(pending.pop(0))
        if not async_ok:
            drain_all()   # sync regime (and the superchunk=1 legacy loop)

    drain_all()
    _tf = obs_begin("final_flush")
    got = jax.device_get(carry)        # one batched fetch, carry incl.
    final = _sim(got)                  # the metrics carry when enabled
    final_mc = got[1] if collect else None
    _HOST_SYNCS[0] += 1
    if retain:
        _scatter_retired(
            bases, np.minimum(w, m - bases).clip(min=0),
            (final.quack_time, final.deliver_time, final.retry,
             final.recv_has),
            (out_quack, out_deliver, out_retry, out_recv))
    obs_end(_tf, "final_flush", cat="drain")

    if not retain:
        drain_sink.on_final(final, final_mc, bases.copy(), w,
                            tuple(growth_events), t)
        return []

    # sanitize the dispatch mirror: a round beyond the run never fired
    ss_all = np.where((send_step >= 0) & (send_step < spec0.steps),
                      send_step, -1).astype(np.int32)

    traj = np.stack(bases_hist)                     # (n_boundaries, n_b)
    all_metrics = _concat_metrics(n_b, metric_parts)
    events = tuple(growth_events)
    out = []
    for b, spec in enumerate(specs):
        metrics = StepMetrics(*(getattr(all_metrics, name)[b]
                                for name in StepMetrics._fields))
        out.append(SimResult(
            spec=spec, metrics=metrics,
            quack_time=out_quack[b], deliver_time=out_deliver[b],
            retry=out_retry[b], recv_has=out_recv[b],
            gc_frontiers=traj[:, b].astype(np.int64),
            final_window_slots=w,
            window_growth_events=events,
            send_step=ss_all[b],
            delivery_latency=_latency_from(ss_all[b], out_deliver[b]),
            obs=(obs_from_final(final_mc, obs_parts, b)
                 if collect else None),
        ))
    return out


def run_simulation_batch(specs: Sequence[SimSpec]) -> List[SimResult]:
    """Run many failure scenarios of one shape in a single compilation.

    All specs must share every non-failure field (same RSMs, schedules,
    thresholds and window config — e.g. from ``build_spec`` with different
    ``FailureScenario`` masks); the failure masks are stacked and the
    runner ``jax.vmap``-ed over them, so a whole sweep costs one compile +
    one device dispatch (per chunk, when windowed) instead of one
    ``lru_cache`` entry per scenario. Windowed specs run on the windowed
    kernel with per-scenario window bases (``_run_windowed_batch``) —
    O(B * W) device state instead of O(B * M) — and are bit-identical to
    per-scenario runs.
    """
    specs = list(specs)
    if not specs:
        return []
    require_uniform_batch(specs)
    if specs[0].window_slots:
        return _run_windowed_batch(specs)
    return _run_dense_batch(specs)


def require_uniform_batch(specs: Sequence[SimSpec]) -> None:
    """Raise unless the specs differ only in their failure masks.

    The shared precondition of every vmapped dispatch: one compilation
    serves the whole batch only when shapes, schedules, thresholds and
    window config agree. Used by ``run_simulation_batch`` and the
    topology engine (where each batch member is one link of the graph).
    """
    nspec = _neutral(specs[0])
    win_key = (specs[0].window_slots, specs[0].chunk_steps,
               specs[0].adaptive_window, specs[0].superchunk,
               specs[0].debug_checks)
    for s in specs[1:]:
        if (_neutral(s) != nspec
                or (s.window_slots, s.chunk_steps, s.adaptive_window,
                    s.superchunk, s.debug_checks)
                != win_key):
            raise ValueError("run_simulation_batch: specs differ outside "
                             "their failure masks; batch members must share "
                             "shapes, schedules, thresholds and window "
                             "config (window_slots / chunk_steps / "
                             "adaptive_window)")
