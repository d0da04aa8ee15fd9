"""One run of one cell: set-up, the measured window, the check, the
metrics.

Set-up builds the cell's ``StreamSession`` from its files, checks that
the program offers exactly the traffic the benchmark generated, and
runs one warm-up session of the same spec, which compiles (or loads from
the persistent cache) every program the window uses. The window then
runs whole sessions back to back and starts none once ``seconds`` have
passed. With ``trace`` the first window session runs under the JAX
profiler. After the window the program's state is dropped, the plain
reference runs once, and every window session is compared with it.
Where the configuration carries failures, set-up draws the crash
placement from the seed once and gives the same placement to the
program and to the reference.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import inspect
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import compare, devtrace
from bench.window import SessionRun, run_one, tapped
from bench.workload import (ROOT, Cell, arrivals, build_session,
                            crash_placement, drain_rounds)

METRICS = Path(__file__).resolve().parent / "metrics"
TRACE_DIR = ROOT / ".bench_out" / "trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def check_device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {dev.platform} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py`` (a
    name may hold dots, so the file is loaded by its path)."""
    key = f"bench.metrics.{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, METRICS / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key].read


def reference(config: dict):
    """The plain reference the configuration names:
    ``bench/reference/<name>.py``."""
    return importlib.import_module(f"bench.reference.{config['reference']}")


@dataclasses.dataclass
class Context:
    """What the metric readers read."""

    cell: Cell
    setup_s: float
    runs: List[SessionRun]           # the window's sessions
    trace: Optional[devtrace.Reduction] = None
    traced_rounds: int = 0
    device_kind: str = ""

    @property
    def served_s(self) -> float:
        """The window sessions' own wall time: the window less the
        harness's bookkeeping between sessions."""
        return sum(r.end_ns - r.start_ns for r in self.runs) / 1e9

    @property
    def steady_runs(self) -> List[SessionRun]:
        """Window sessions not slowed by the profiler (all of them, if
        the traced one is the only one)."""
        plain = [r for r in self.runs if not r.traced]
        return plain or self.runs


def placement(cell: Cell, seed: int) -> Optional[dict]:
    """The seed's crash placement of the cell's configuration (None
    without failures); a crashed run is never checked against a
    reference that models no failure."""
    failures = crash_placement(cell.config, seed)
    if failures is not None and "failures" not in inspect.signature(
            reference(cell.config).run).parameters:
        raise ValueError(
            f"configuration {cell.config['name']!r} carries failures but "
            f"its reference {cell.config['reference']!r} models none")
    return failures


def prepare(cell: Cell, seed: int):
    """The cell's session under the seed's crash placement, after
    checking it offers the generated traffic for the rounds the
    reference will run."""
    failures = placement(cell, seed)
    session = build_session(cell, failures)
    counts = arrivals(cell.traffic, seed)
    steps = len(counts) + drain_rounds(cell.config, cell.traffic)
    if not np.array_equal(np.asarray(session.arrivals), counts):
        raise ValueError("the program's arrival schedule differs from "
                         "the benchmark's traffic generator")
    if session.spec.steps != steps:
        raise ValueError(f"the program runs {session.spec.steps} rounds "
                         f"a session; the benchmark expects {steps}")
    return session, counts, steps, failures


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t0: float, log=print) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    from repro.compile_cache import use_compile_cache

    cache = use_compile_cache()
    # cache every program, however quick to compile, so a run's set-up
    # after the first finds all of them there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    session, counts, steps, failures = prepare(cell, seed)
    spec = session.spec
    log(f"cell {cell.name}: n={spec.n_s}<->{spec.n_r} links={cell.links} "
        f"chained={cell.chained} horizon={spec.m} rate="
        f"{session.config.process.rate} steps={steps} chunk="
        f"{spec.chunk_steps} W={spec.window_slots} superchunk="
        f"{spec.superchunk} compile_cache={cache}")
    if failures is not None:
        log(f"crash placement: crash_s={list(failures['crash_s'])} "
            f"crash_r={list(failures['crash_r'])}")
    rng = np.random.default_rng(seed)
    pick = lambda: int(rng.integers(cell.links))  # noqa: E731
    runs: List[SessionRun] = []
    offset_ns = 0.0
    with tapped() as slot:
        warm = run_one(session, slot, lane=pick())
        setup_s = time.monotonic() - t0
        log(f"set-up {setup_s:.3f}s (warm-up session {warm.seconds:.3f}s, "
            f"{warm.counters.get('traces', 0)} traces)")
        win0 = time.monotonic_ns()
        while not runs or time.monotonic_ns() - win0 < seconds * 1e9:
            traced = trace and not runs
            if traced:
                devtrace.start(str(TRACE_DIR))
                with jax.profiler.TraceAnnotation(devtrace.SESSION):
                    offset_ns = -time.monotonic_ns()
                    runs.append(run_one(session, slot, pick(), True))
                devtrace.stop()
            else:
                runs.append(run_one(session, slot, pick()))
            r = runs[-1]
            log(f"session {len(runs)}: {r.seconds:.3f}s rounds="
                f"{r.counters.get('rounds')} dispatches="
                f"{r.counters.get('dispatches')} traces="
                f"{r.counters.get('traces')} problems={r.problems}")
    device = jax.devices()[0]
    stats = [d.memory_stats() or {} for d in jax.devices()]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    reduction = None
    if trace:
        reduction = _reduce_trace(runs[0], offset_ns)
    del session                       # the program's state goes first
    gc.collect()

    t_ref = time.monotonic()
    ref_mod = reference(cell.config)
    crashes = {} if failures is None else {"failures": failures}
    lanes = ref_mod.run(cell.config, counts, links=cell.links,
                        chained=cell.chained, steps=steps,
                        chunk=cell.chunk_steps, **crashes)
    ref_for = lambda lane: ref_mod.as_record(  # noqa: E731
        lanes, links=cell.links, chained=cell.chained,
        chunk=cell.chunk_steps, steps=steps, sample_lane=lane)
    offered = cell.horizon * cell.links
    numbers = compare.checks(runs, ref_for, offered, warmup=warm)
    log(f"reference and check {time.monotonic() - t_ref:.3f}s")
    failed = sum(max(offered - (r.record.delivered if r.record else 0), 0)
                 for r in runs)

    ctx = Context(cell=cell, setup_s=setup_s, runs=runs, trace=reduction,
                  traced_rounds=int(runs[0].counters.get("rounds", 0)),
                  device_kind=device.device_kind)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics: Dict[str, dict] = {}
    for m in wanted:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": compare.passed(numbers),
           "attempted": offered * len(runs), "failed": failed,
           "metrics": metrics, "device": dev}
    if reduction is not None:
        dev["busy_s"] = reduction.busy_s
        dev["window_s"] = reduction.window_s
        out["breakdown"] = {"device_ops": reduction.device_ops,
                            "idle_gaps": reduction.idle_gaps}
    out["checks"] = numbers
    return out


def _reduce_trace(run: SessionRun, offset_ns: float
                  ) -> Optional[devtrace.Reduction]:
    """Reduce the traced session; ``offset_ns`` is minus the host clock
    as the session's annotation began (the trace's clock starts there
    at the annotation's own start)."""
    events = devtrace.read_events(str(TRACE_DIR))
    marks = [e for e in events if e.name == devtrace.SESSION]
    if not marks:
        return None
    shift = marks[0].start_ns + offset_ns
    host = [(s.name, s.start_ns, s.start_ns + s.dur_ns) for s in run.spans]
    host += [("sink", a, b) for a, b in run.sink_spans]
    return devtrace.reduce_events(events, host, clock_offset_ns=shift)


def main_result_lines(out: dict) -> None:
    """Print the compared numbers as the last lines of standard error,
    then the result as the last line of standard output."""
    for name, v in out["checks"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(_json(out), flush=True)


def _json(out: dict) -> str:
    import json

    return json.dumps(out, default=lambda x: x.item()
                      if hasattr(x, "item") else str(x))
