"""Without a TPU the benchmark exits non-zero and prints no result; in a
directory that holds only ``BENCHMARK.json`` and the benchmark's own
files (no system under test) it does the same."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.workload import ROOT


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lan4_fleet",
         "--seed", str(2 ** 34 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


@pytest.mark.parametrize("where", ["checkout", "benchmark_only"])
def test_refuses_without_a_chip(where, tmp_path):
    cwd = ROOT
    if where == "benchmark_only":
        shutil.copytree(ROOT / "bench", tmp_path / "bench")
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        cwd = tmp_path
    out = _run(cwd)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
