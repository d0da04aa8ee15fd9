"""``chip_smoke.py``: every phase at tiny sizes on the CPU, and the
script's refusal to report success without a TPU.

The script sits at the repository root, which is not on ``sys.path``,
so it is loaded by path. Its phases take their sizes as arguments; here
they run at n = 4, 2 lanes and 512 messages, with the Pallas kernel in
interpret mode.
"""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_phase(smoke):
    dev = smoke.check_device(platform="cpu")
    assert dev == {"platform": "cpu", "kind": "cpu", "count": 1}
    with pytest.raises(RuntimeError, match="no tpu device"):
        smoke.check_device()


def test_stream_phase_tiny(smoke):
    result = smoke.run_stream(f=1, links=2, horizon=512)
    assert result.delivered == 2 * 512
    assert result.counters["traces"] == 0          # the warm run


def test_oracle_and_kernel_phases_tiny(smoke):
    spec, reference = smoke.run_oracle(f=1, n_msgs=512)
    assert spec.window_slots < spec.m              # the window rotates
    assert spec.debug_checks
    assert len(reference.gc_frontiers) > 1
    smoke.run_kernel(spec, reference, expect_custom_call=False)


def _run_script(path, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_main_fails_without_tpu():
    out = _run_script(SCRIPT, REPO)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no tpu device" in out.stderr


def test_script_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run_script(str(lone), str(tmp_path))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
