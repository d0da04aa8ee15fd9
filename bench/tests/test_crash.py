"""Crash-stop failures carried by a configuration: the seed's placement
reaches the program and the crash-stop reference alike, the two agree on
every compared number, and the check fails where they are not held to
the same placement, where the control stands in, or where the
configuration names a reference that models no failure. The cells
without failures keep their sessions, lane picks and reference call."""

import copy
import hashlib
import json
import shutil
import time

import pytest

from bench import control, harness
from bench.reference import picsou_crash_stop, picsou_failure_free
from bench.workload import (ROOT, arrivals, build_session, crash_placement,
                            drain_rounds, find_cell, load_benchmark)
from tiny import tiny_cell

SEEDS = (3, 2 ** 33 + 5)


def crash_cell(tmp_path, crash_s, crash_r, at_round, rate=4.0,
               horizon=800, reference="picsou_crash_stop"):
    """A cell of ``bft4_lan_100b`` widths with crash-stop failures, 2
    links at ``rate`` messages a round, found from files written as a
    later configuration and traffic mix would be."""
    name = f"lan4_crash_{crash_s}_{crash_r}_{at_round}"
    if not (tmp_path / "bench").exists():
        shutil.copytree(ROOT / "bench", tmp_path / "bench")
    config = json.loads((ROOT / "bench/configs/bft4_lan_100b.json")
                        .read_text())
    config.update(name=name, reference=reference,
                  failures={"kind": "crash_stop", "crash_s": crash_s,
                            "crash_r": crash_r, "at_round": at_round})
    (tmp_path / f"bench/configs/{name}.json").write_text(json.dumps(config))
    traffic = json.loads((ROOT / "bench/traffic/fleet32_const12.json")
                         .read_text())
    traffic.update(horizon=horizon, links=2,
                   arrivals={"kind": "constant", "rate": rate})
    (tmp_path / f"bench/traffic/{name}.json").write_text(json.dumps(traffic))
    bench = load_benchmark()
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": name,
                               "traffic": name, "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return find_cell(name, root=tmp_path)


def run(cell, seed):
    return harness.run_cell(cell, seed, 0.5, False, time.monotonic(),
                            log=lambda msg: None)


# one sender, one receiver, or one of each crashed, from round 32 or
# from round 0; one of each at 4.0 msg/round leaves messages undelivered
# (below), so those run at 1.0
CASES = [(1, 0, 32, 4.0), (0, 1, 32, 4.0), (1, 0, 0, 4.0), (0, 1, 0, 4.0),
         (1, 1, 0, 1.0), (1, 1, 32, 1.0)]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("crash_s,crash_r,at_round,rate", CASES)
def test_program_agrees_with_crash_stop_reference(tmp_path, crash_s,
                                                  crash_r, at_round, rate,
                                                  seed):
    cell = crash_cell(tmp_path, crash_s, crash_r, at_round, rate=rate)
    placed = crash_placement(cell.config, seed)
    assert sorted(placed["crash_s"]) == [-1] * (4 - crash_s) \
        + [at_round] * crash_s
    assert sorted(placed["crash_r"]) == [-1] * (4 - crash_r) \
        + [at_round] * crash_r
    out = run(cell, seed)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(v["value"] == 0 for v in out["checks"].values())


@pytest.mark.parametrize("at_round", [0, 32])
def test_two_sided_crash_at_full_rate_agrees(tmp_path, at_round):
    # one sender and one receiver down at 4.0 msg/round: recovery falls
    # behind and the session ends with messages undelivered; the program
    # and the reference agree on every other number, and on how many
    cell = crash_cell(tmp_path, 1, 1, at_round)
    seed = SEEDS[0]
    out = run(cell, seed)
    checks = out["checks"]
    assert all(v["value"] == 0 for k, v in checks.items()
               if k != "undelivered"), checks
    counts = arrivals(cell.traffic, seed)
    lanes = picsou_crash_stop.run(
        cell.config, counts, links=cell.links, chained=False,
        steps=len(counts) + drain_rounds(cell.config, cell.traffic),
        chunk=cell.chunk_steps,
        failures=crash_placement(cell.config, seed))
    short = cell.horizon - lanes[0].n_delivered
    offered = cell.horizon * cell.links
    assert out["failed"] == short * cell.links * out["attempted"] // offered
    assert checks["undelivered"]["value"] == \
        short * cell.links * (out["attempted"] // offered + 1)


def test_control_on_a_crash_configuration_is_not_correct(tmp_path):
    cell = crash_cell(tmp_path, 1, 1, 32, rate=1.0, horizon=400)
    readings = control.readings(cell, seed=SEEDS[1])
    assert not readings["correct"]
    assert readings["sound"]["correct"]
    assert all(v["value"] == 0 for v in readings["sound"]["checks"].values())


def test_program_on_another_seeds_placement_is_not_correct(tmp_path,
                                                           monkeypatch):
    cell = crash_cell(tmp_path, 1, 1, 32, rate=1.0, horizon=400)
    seed, other = SEEDS
    assert crash_placement(cell.config, seed) != \
        crash_placement(cell.config, other)
    real = harness.build_session
    monkeypatch.setattr(
        harness, "build_session",
        lambda cell, failures: real(cell, crash_placement(cell.config,
                                                          other)))
    out = run(cell, seed)
    assert not out["correct"]
    assert out["checks"]["message_diff"]["value"] > 0


def test_failures_with_a_failure_free_reference_is_an_error(tmp_path):
    cell = crash_cell(tmp_path, 1, 0, 32, reference="picsou_failure_free")
    with pytest.raises(ValueError, match="models none"):
        run(cell, SEEDS[0])
    with pytest.raises(ValueError, match="models none"):
        control.readings(cell, SEEDS[0])


def test_unknown_failure_kind_is_an_error(tmp_path):
    cell = crash_cell(tmp_path, 1, 0, 32)
    config = copy.deepcopy(cell.config)
    config["failures"]["kind"] = "byzantine"
    with pytest.raises(ValueError, match="unknown failure kind"):
        crash_placement(config, SEEDS[0])
    config["failures"].update(kind="crash_stop", crash_r=5)
    with pytest.raises(ValueError, match="of 4 replicas"):
        crash_placement(config, SEEDS[0])


# the existing cells as the parent tree built them: a digest of each
# session's whole spec, and the lanes the check samples on SEEDS[1]
SPECS = {
    "geo19_fleet":
        "ad817f484eb66f390bceeb30d68ddec421f8b19305705de5581cd80cd5abbafc",
    "lan4_fleet":
        "c74ec1b20b8026a7690dd8ff1ff54806aebb4494bfa0757f31f5076292b57a58",
}
PICKS = {
    2: [1, 0, 1, 0, 0, 1, 1, 1, 0, 0, 1, 0, 1, 0, 1, 0, 1, 0, 1, 0, 1, 1],
    32: [25, 7, 22, 13, 2, 18, 17, 22, 13, 1, 16, 15, 27, 6, 27, 3, 19, 8,
         30, 3, 16, 16],
}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_cells_without_failures_are_unchanged(name, monkeypatch):
    cell = find_cell(name)
    assert crash_placement(cell.config, SEEDS[1]) is None
    spec = build_session(cell).spec
    assert hashlib.sha256(repr(spec).encode()).hexdigest() == SPECS[name]

    lanes, calls = [], []
    real_run_one, real_ref = harness.run_one, picsou_failure_free.run

    def run_one(session, slot, lane, traced=False):
        lanes.append(lane)
        return real_run_one(session, slot, lane, traced)

    def ref_run(*args, **kwargs):
        calls.append(sorted(kwargs))
        return real_ref(*args, **kwargs)

    monkeypatch.setattr(harness, "run_one", run_one)
    monkeypatch.setattr(picsou_failure_free, "run", ref_run)
    small = tiny_cell(name, links=cell.links)
    out = harness.run_cell(small, SEEDS[1], 0.5, False, time.monotonic(),
                           log=lambda msg: None)
    assert out["correct"], out["checks"]
    assert 2 <= len(lanes) <= len(PICKS[cell.links])
    assert lanes == PICKS[cell.links][:len(lanes)]
    assert calls == [["chained", "chunk", "links", "steps"]]
