"""Every cell's session path, at tiny sizes on the CPU: the window runs,
every session is checked against the reference and passes, and the
result carries the cell's metrics."""

import pytest

from bench.workload import find_cell, load_benchmark
from tiny import run_tiny

# the cells, and the relay mix on the LAN clusters: chained links, the
# path of a relay cell (PERF.md, open questions)
CASES = [(w["name"], None) for w in load_benchmark()["workloads"]]
CASES.append(("lan4_fleet", "relay4_const12"))


@pytest.mark.parametrize("name,traffic", CASES)
def test_cell_runs_and_passes(name, traffic):
    out = run_tiny(name, traffic=traffic)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"]
                                   for m in find_cell(name).end_to_end}
    assert {"msgs_per_s", "setup_s"} <= set(out["metrics"])
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(v["limit"] == 0 and v["value"] == 0
               for v in out["checks"].values())


def test_traced_run_reports_host_layers():
    # no device trace on the CPU: the device readers find nothing and
    # their metrics are left out, never reported as 0; what the program
    # itself counts and spans is there
    out = run_tiny("geo19_fleet", trace=True, traffic="relay4_const12")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {
        m["name"] for m in find_cell("geo19_fleet").per_layer
        if m["source"] in ("program_span", "program_counter")}
    # commit floors force one 16-round chunk per dispatch (the last
    # one shorter)
    assert 62.5 <= out["metrics"]["dispatches_per_kround"]["value"] < 70
