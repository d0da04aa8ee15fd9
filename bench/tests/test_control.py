"""The control fails the check: the reference with its QUACK quorum one
receiver short (u instead of u + 1, breaking the configuration's
``quack_quorum`` guarantee), put in the program's place, at a size a
test run holds. ``bench/control.py`` runs the same at the cells' own
sizes."""

import pytest

from bench import control
from bench.workload import load_benchmark
from tiny import tiny_cell

CASES = [(w["name"], None) for w in load_benchmark()["workloads"]]
CASES.append(("lan4_fleet", "relay4_const12"))


@pytest.mark.parametrize("name,traffic", CASES)
def test_control_is_not_correct(name, traffic):
    cell = tiny_cell(name, horizon=600, traffic=traffic)
    readings = control.readings(cell, seed=7)
    assert not readings["correct"]
    assert readings["sound"]["correct"]
    assert all(v["value"] == 0 for v in readings["sound"]["checks"].values())
