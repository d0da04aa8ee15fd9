"""Cells of the benchmark cut to sizes the CPU runs in seconds: the
configuration's clusters at n = 4 and a short stream on two or four
links, through the same harness and program path as a chip run."""

import copy
import json
import time

from bench import harness
from bench.workload import BENCH, find_cell

SMALL = {"n": 4, "u": 1, "r": 1}


def tiny_cell(name, horizon=400, links=2, traffic=None):
    """Cell ``name`` cut to size; ``traffic`` names another mix of
    ``bench/traffic`` to offer in place of the cell's own."""
    cell = copy.deepcopy(find_cell(name))
    if traffic is not None:
        with open(BENCH / "traffic" / f"{traffic}.json") as f:
            cell.traffic = json.load(f)
    if cell.config["sender"]["n"] > 4:
        cell.config["sender"] = dict(SMALL)
        cell.config["receiver"] = dict(SMALL)
    cell.traffic["horizon"] = horizon
    cell.traffic["links"] = 4 if cell.chained else links
    return cell


def run_tiny(name, seed=2 ** 33 + 5, trace=False, **sizes):
    """One harness run of cell ``name`` at tiny sizes on this machine
    (the harness's look for a chip is skipped)."""
    cell = tiny_cell(name, **sizes)
    return harness.run_cell(cell, seed, 0.5, trace, time.monotonic(),
                            log=lambda msg: None)
