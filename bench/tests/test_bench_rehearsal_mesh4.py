"""A four-chip cell of bf16 updates rehearsed on four CPU devices
(``mesh_rehearsal.py``, in a process of its own that sees four
devices): correct on the mesh engine as it stands, each round checked as
it ends, and not correct with a fault planted under the timed path."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import rehearsal

PARAMS = 10_007   # ragged against four shards


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "tests" / "mesh_rehearsal.py"),
         "--params", str(PARAMS), "--seconds", "1", "--faults", "--trace"],
        env=env, cwd=str(harness.ROOT), capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()]
    return {(x["mesh"]["fault"], x["mesh"]["trace"]): x for x in lines}


def test_mesh_rehearsal_is_correct(runs):
    line = runs[("", False)]
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"setup_s", "round_close_ms"} <= set(line["metrics"])
    assert list(line)[-2:] == ["checks", "mesh"]


def test_a_mesh_step_did_the_folding(runs):
    mesh = runs[("", False)]["mesh"]
    assert mesh["mesh_step_folded"], mesh["fold_steps"]
    assert mesh["local_compiles"] == 0 and mesh["mesh_compiles"] >= 1


def test_each_round_keeps_no_fused_vector(runs):
    mesh = runs[("", False)]["mesh"]
    assert mesh["rounds_checked"] > 0 and mesh["vectors_kept"] == 0
    assert len(mesh["check_s"]) == mesh["rounds_checked"] == \
        mesh["checks_paused"]


def test_the_control_is_not_correct_on_the_same_rounds(runs):
    line = runs[("", False)]
    assert line["checks"]["max_rel_err"]["value"] < \
        harness.LIMITS["max_rel_err"] < line["mesh"]["control_max_rel_err"]


def test_traced_mesh_rehearsal_reads_per_layer_metrics(runs):
    line = runs[("", True)]
    assert line["correct"], line["checks"]
    assert {"compiles_in_window", "engine_fold_ms",
            "device_idle_in_close_pct"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]


@pytest.mark.parametrize("fault", sorted(rehearsal.FAULTS))
def test_planted_fault_is_not_correct_on_the_mesh(runs, fault):
    line = runs[(fault, False)]
    assert line["mesh"]["mesh_step_folded"]
    assert not line["correct"], line["checks"]
