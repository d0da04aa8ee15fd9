#!/usr/bin/env python3
"""Smoke test of the C3B streaming engine on one TPU chip.

    python chip_smoke.py

Drives the served path once through the entry points a user calls, at
the paper's largest §6.1 cluster (BFT n = 19 <-> 19), and checks what
comes out. The phases run in order in this one process; the first
failure raises, so the exit code is non-zero and no result is printed.

1. device — JAX sees a TPU. There is no CPU fallback.
2. stream — a ``StreamSession`` of 32 lanes x 65,536 messages, constant
   arrivals at 90% of analytic capacity, run cold (compile included)
   and then warm. Every message is delivered, the live-vs-device checks
   find no problem, and no SLO watchdog ends in breach.
3. oracle — an n = 19 pair of 1,024 messages with a quarter of each
   cluster crashed, run with ``debug_checks`` (transfer guard, window
   base mirror, retirement safety). It equals ``refsim.run_reference``
   of the same spec bit for bit.
4. kernel — the same spec through the Pallas QUACK kernel: bit-identical
   to phase 3, and its compiled program holds ``tpu_custom_call``.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Times printed on the way are smoke output, not benchmark numbers. Each
phase is a function whose sizes are arguments, so the tests run the
same code at tiny sizes on the CPU.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import FailureScenario, RSMConfig, SimConfig  # noqa: E402
from repro.core.refsim import run_reference  # noqa: E402
from repro.core.simulator import (run_simulation,  # noqa: E402
                                  superchunk_program)
from repro.stream import ArrivalProcess, StreamConfig  # noqa: E402
from repro.stream import StreamSession  # noqa: E402

# engine outputs compared against the numpy oracle (as tests/test_replay)
OUTPUTS = ("quack_time", "deliver_time", "retry", "recv_has")
ORACLE_METRICS = ("resends", "cross_msgs")


def check_device(platform: str = "tpu") -> dict:
    """Phase 1: report what JAX sees; raise unless it is ``platform``."""
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; devices {devices}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != platform:
        raise RuntimeError(
            f"JAX found no {platform} device (it runs on {dev.platform}); "
            f"this smoke test has no fallback")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def _stream_problems(result, expected: int) -> list:
    problems = list(result.problems)
    if result.delivered != expected:
        problems.append(f"delivered {result.delivered} of {expected}")
    breaches = [e.kind for e in result.slo_events if not e.recovered]
    if breaches:
        problems.append(f"SLO events without recovery: {breaches}")
    return problems


def run_stream(f: int = 6, links: int = 32, horizon: int = 65_536,
               utilization: float = 0.9, chunk_steps: int = 16,
               superchunk: int = 8):
    """Phase 2: the served path, a BFT(f) <-> BFT(f) streaming session."""
    cluster = RSMConfig.bft(f)
    sim = SimConfig(window=4, phi=6, window_slots="auto",
                    chunk_steps=chunk_steps, superchunk=superchunk)
    cfg = StreamConfig(horizon=horizon,
                       process=ArrivalProcess(kind="constant"),
                       utilization=utilization, links=links)
    t0 = time.perf_counter()
    session = StreamSession(cluster, cluster, sim, cfg)
    setup_s = time.perf_counter() - t0
    print(f"stream: n={cluster.n}<->{cluster.n} lanes={links} "
          f"horizon={horizon} rate={session.config.process.rate:.3f} "
          f"msg/round W={session.spec.window_slots} "
          f"set-up {setup_s:.3f}s")
    expected = links * horizon
    result = None
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        result = session.run()
        run_s = time.perf_counter() - t0
        c = result.counters
        print(f"stream {label} run {run_s:.3f}s"
              f"{' (compile included)' if label == 'cold' else ''}: "
              f"dispatches={c['dispatches']} traces={c['traces']} "
              f"syncs={c['syncs']} rounds={result.rounds}")
        print(result.summary())
        problems = _stream_problems(result, expected)
        if problems:
            raise AssertionError(f"stream {label} run: "
                                 + "; ".join(problems))
    return result


def _oracle_spec(f: int, n_msgs: int, crash: float, seed: int,
                 utilization: float):
    """The spec a streaming session of ``n_msgs`` messages runs (arrival
    paced, so the window stays narrower than the stream and rotates)
    with a fraction ``crash`` of each cluster crashed from round 0."""
    cluster = RSMConfig.bft(f)
    sim = SimConfig(window=4, phi=6, window_slots="auto", chunk_steps=16,
                    superchunk=8, debug_checks=True)
    cfg = StreamConfig(horizon=n_msgs,
                       process=ArrivalProcess(kind="constant"),
                       utilization=utilization)
    failures = FailureScenario.crash_fraction(cluster.n, cluster.n, crash,
                                              seed=seed)
    return StreamSession(cluster, cluster, sim, cfg, failures).spec


def _mismatches(a, b, fields) -> list:
    return [name for name in fields
            if not np.array_equal(np.asarray(getattr(a, name)),
                                  np.asarray(getattr(b, name)))]


def run_oracle(f: int = 6, n_msgs: int = 1024, crash: float = 0.25,
               seed: int = 0, utilization: float = 0.9):
    """Phase 3: engine vs numpy oracle under crashes, debug checks on."""
    spec = _oracle_spec(f, n_msgs, crash, seed, utilization)
    t0 = time.perf_counter()
    result = run_simulation(spec)
    run_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = run_reference(spec)
    ref_s = time.perf_counter() - t0
    delivered = int((result.deliver_time >= 0).sum())
    print(f"oracle: n={spec.n_s}<->{spec.n_r} msgs={spec.m} "
          f"steps={spec.steps} W={spec.window_slots} crashed="
          f"{sum(c >= 0 for c in spec.crash_s)}+"
          f"{sum(c >= 0 for c in spec.crash_r)} delivered={delivered} "
          f"engine {run_s:.3f}s (compile included), oracle {ref_s:.3f}s")
    bad = _mismatches(result, ref, OUTPUTS + ("gc_frontiers",))
    bad += [f"metrics.{name}" for name in ORACLE_METRICS
            if not np.array_equal(
                np.asarray(getattr(result.metrics, name)),
                np.asarray(getattr(ref, name)))]
    if bad:
        raise AssertionError(f"engine differs from the numpy oracle in "
                             f"{bad}")
    return spec, result


def run_kernel(spec, reference, expect_custom_call: bool = True):
    """Phase 4: ``spec`` through the Pallas QUACK kernel must equal
    ``reference`` (the jnp-quorum run of phase 3) bit for bit, and the
    superchunk program holds a compiled kernel iff
    ``expect_custom_call``."""
    pspec = dataclasses.replace(spec, use_pallas_quack=True)
    t0 = time.perf_counter()
    result = run_simulation(pspec)
    run_s = time.perf_counter() - t0
    bad = _mismatches(result, reference,
                      OUTPUTS + ("gc_frontiers", "delivery_latency"))
    bad += [f"metrics.{name}" for name in type(result.metrics)._fields
            if not np.array_equal(
                np.asarray(getattr(result.metrics, name)),
                np.asarray(getattr(reference.metrics, name)))]
    if bad:
        raise AssertionError(f"Pallas quorum run differs from the jnp run "
                             f"in {bad}")
    program, args = superchunk_program(pspec)
    text = program.lower(*args).compile().as_text()
    calls = text.count("tpu_custom_call")
    print(f"kernel: Pallas quorum run {run_s:.3f}s (compile included), "
          f"bit-identical to the jnp run; tpu_custom_call x{calls} in "
          f"the superchunk program")
    if (calls > 0) != expect_custom_call:
        raise AssertionError(
            f"superchunk program has {calls} tpu_custom_call; expected "
            f"{'some' if expect_custom_call else 'none'}")
    return result


def main() -> int:
    device = check_device()
    print(f"compile cache: {use_compile_cache()}")
    run_stream()
    spec, reference = run_oracle()
    run_kernel(spec, reference)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
