"""The command refuses to run without a chip of a kind it knows, and
prints no result then."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
TABLE = harness.load_json(harness.BENCH / "peaks.json")["devices"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "xdev_cnn46_int8.store_rounds", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode == 2 and out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_run_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0 and out.stdout == ""


@pytest.mark.parametrize("info,chips", [
    ({"platform": "cpu", "kind": "cpu", "count": 1}, 1),
    (dict(V5E, kind="TPU v9 imaginary"), 1),
    (V5E, 4),
])
def test_device_check_refuses(info, chips):
    with pytest.raises(harness.NoDevice):
        harness.check_device(info, chips, TABLE)


def test_device_check_accepts_a_v5e():
    harness.check_device(V5E, 1, TABLE)
