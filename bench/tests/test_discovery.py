"""The harness finds everything by the names in ``BENCHMARK.json``: a
later cell is a traffic file and a ``workloads`` entry, with no other
file edited."""

import json
import shutil

import pytest

from bench import harness
from bench.workload import ROOT, find_cell, load_benchmark


def test_every_name_resolves():
    bench = load_benchmark()
    for w in bench["workloads"]:
        cell = find_cell(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert harness.reference(cell.config).run
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_new_cell_found_without_editing_a_file(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = load_benchmark()
    bench["workloads"].append(
        {"name": "lan4_fleet8", "config": "bft4_lan_100b",
         "traffic": "fleet8_const12", "chips": 1,
         "why": "a cell added as data"})
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append("lan4_fleet8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.loads(
        (ROOT / "bench/traffic/fleet32_const12.json").read_text())
    traffic["links"] = 8
    (tmp_path / "bench/traffic/fleet8_const12.json").write_text(
        json.dumps(traffic))
    cell = find_cell("lan4_fleet8", root=tmp_path)
    assert cell.links == 8 and cell.config["sender"]["n"] == 4
    assert len(cell.per_layer) == len(bench["per_layer"])
    with pytest.raises(KeyError):
        find_cell("no_such_cell", root=tmp_path)
