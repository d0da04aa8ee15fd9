"""Cells of the benchmark, found by name, and the traffic they offer.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: it names a
configuration (``configs[].file``, a deployment: the two RSMs, the
network, the protocol's widths, the yardstick window and the guarantees)
and a traffic mix (``bench/traffic/<traffic>.json``: arrivals, horizon,
links, chaining, chunk length). Nothing here knows a cell by name, so a
later cell is a new traffic file and a new entry, with no code edited.

A configuration may carry crash-stop failures::

    "failures": {"kind": "crash_stop", "crash_s": 6, "crash_r": 6,
                 "at_round": 0}

that many replicas of the sending and of the receiving RSM are down from
``at_round`` on; which ones, the seed draws (:func:`crash_placement`).
Without the key no replica crashes.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # metric entries this cell reports
    per_layer: List[dict]

    @property
    def links(self) -> int:
        return int(self.traffic["links"])

    @property
    def chained(self) -> bool:
        return bool(self.traffic["chained"])

    @property
    def chunk_steps(self) -> int:
        return int(self.traffic["chunk_steps"])

    @property
    def horizon(self) -> int:
        return int(self.traffic["horizon"])


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: Optional[dict] = None,
              root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic files read."""
    bench = bench if bench is not None else load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def arrivals(traffic: dict, seed: int) -> np.ndarray:
    """Messages arriving in each round of one link, ``horizon`` in all.

    ``constant``: ``rate`` per round by exact fractional accumulation,
    so every seed offers the same schedule (a different schedule is a
    different compiled program, see PERF.md); the seed only draws which
    lanes the check samples.
    """
    spec = traffic["arrivals"]
    horizon = int(traffic["horizon"])
    if spec["kind"] != "constant":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    rate = float(spec["rate"])
    counts, total, frac = [], 0, 0.0
    while total < horizon:
        frac += rate
        got = int(frac)
        frac -= got
        counts.append(got)
        total += got
    counts[-1] -= total - horizon
    return np.asarray(counts, dtype=np.int64)


def drain_rounds(config: dict, traffic: dict) -> int:
    """Rounds a session runs after its last arrival (the program's rule,
    pinned here so the reference runs as many rounds)."""
    s, r = config["sender"]["n"], config["receiver"]["n"]
    return int(traffic["chunk_steps"]) + s + r \
        + 2 * config["protocol"]["phi"] + 96


# the stream of the seed's draws that places crashes, apart from the
# default stream the harness draws the check's lanes from
PLACEMENT_STREAM = 1


def crash_placement(config: dict, seed: int) -> Optional[dict]:
    """The crash round of each replica of each RSM (-1: never) under the
    configuration's ``failures``, or None where it has none.

    Returns ``{"crash_s": (n_s,) rounds, "crash_r": (n_r,) rounds}``:
    the replicas that crash are drawn from ``seed`` by a generator of
    their own, so the lanes the check samples from ``seed`` are the same
    with or without failures.
    """
    spec = config.get("failures")
    if spec is None:
        return None
    if spec.get("kind") != "crash_stop":
        raise ValueError(f"unknown failure kind {spec.get('kind')!r}")
    at = int(spec["at_round"])
    if at < 0:
        raise ValueError(f"failures.at_round {at} is before round 0")
    rng = np.random.default_rng([seed, PLACEMENT_STREAM])
    out = {}
    for side, key in (("sender", "crash_s"), ("receiver", "crash_r")):
        n, k = int(config[side]["n"]), int(spec[key])
        if not 0 <= k <= n:
            raise ValueError(f"failures.{key} = {k} of {n} replicas")
        rounds = np.full(n, -1, np.int64)
        rounds[rng.choice(n, size=k, replace=False)] = at
        out[key] = tuple(int(x) for x in rounds)
    return out


def build_session(cell: Cell, failures: Optional[dict] = None):
    """The program's ``StreamSession`` for ``cell``, as a user builds it,
    with the crash placement ``failures`` (:func:`crash_placement`)."""
    from repro.core import FailureScenario, RSMConfig, SimConfig
    from repro.core.types import NetworkModel
    from repro.stream import ArrivalProcess, StreamConfig, StreamSession

    conf, traffic = cell.config, cell.traffic
    rsm = lambda d: RSMConfig(n=d["n"], u=d["u"], r=d["r"])  # noqa: E731
    proto = conf["protocol"]
    sim = SimConfig(window=proto["window"], phi=proto["phi"],
                    scheduler=proto["scheduler"], window_slots="auto",
                    chunk_steps=cell.chunk_steps)
    cfg = StreamConfig(
        horizon=cell.horizon,
        process=ArrivalProcess(kind=traffic["arrivals"]["kind"],
                               rate=float(traffic["arrivals"]["rate"])),
        net=NetworkModel(**conf["network"]), links=cell.links,
        chained=cell.chained, report_every=int(traffic["report_every"]))
    crashes = None if failures is None else FailureScenario(
        crash_s=failures["crash_s"], crash_r=failures["crash_r"])
    return StreamSession(rsm(conf["sender"]), rsm(conf["receiver"]), sim,
                         cfg, failures=crashes)
