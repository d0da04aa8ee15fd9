"""The least bytes of a round count the carried window state that the
program's scan really carries, and the peaks table refuses a chip it
does not know."""

import pytest

from bench import roofline
from bench.workload import find_cell, load_benchmark
from repro.core import RSMConfig, SimConfig
from repro.core.simulator import build_spec

CELLS = sorted({w["name"] for w in load_benchmark()["workloads"]})


@pytest.mark.parametrize("name", CELLS)
def test_window_cells_match_the_carried_state(name):
    conf = find_cell(name).config
    n_s, n_r = conf["sender"]["n"], conf["receiver"]["n"]
    w = conf["window_slots"]
    rsm = RSMConfig.bft(conf["sender"]["f"])
    spec = build_spec(rsm, rsm, SimConfig(n_msgs=4 * w, window_slots=w,
                                          chunk_steps=16))
    cells = roofline.window_cells(n_s, n_r, w)
    # the scan state holds each flag in a byte and each stamp in an
    # int32, beside a few per-replica counters that do not grow with W
    fixed = 4 * (n_s * n_r + n_r * n_s + n_r + 2)
    assert cells["flags"] + 4 * cells["stamps"] + fixed == \
        spec.scan_state_nbytes()


def test_least_bytes_at_the_cells_sizes():
    geo = find_cell("geo19_fleet")
    lan = find_cell("lan4_fleet")
    assert roofline.least_bytes_per_round(geo.config, geo.links) == \
        pytest.approx(81.95e6 * geo.links / 32, rel=1e-3)
    assert roofline.least_bytes_per_round(lan.config, lan.links) == \
        pytest.approx(1.2508e6, rel=1e-3)


def test_unknown_chip_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
