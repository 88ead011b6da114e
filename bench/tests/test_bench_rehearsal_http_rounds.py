"""xsilo_vgg16_fp32.http_rounds rehearsed on the CPU at a tiny size: correct as it stands,
and not correct with a fault planted under the timed path."""
import pytest

from bench.tests import rehearsal

CELL = "xsilo_vgg16_fp32.http_rounds"


def test_rehearsal_is_correct(tmp_path):
    line = rehearsal.rehearse(CELL, tmp_path)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(rehearsal.FAULTS))
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, fault):
    rehearsal.plant(monkeypatch, fault)
    line = rehearsal.rehearse(CELL, tmp_path)
    assert not line["correct"], line["checks"]


def test_traced_rehearsal_reads_per_layer_metrics(tmp_path):
    line = rehearsal.rehearse(CELL, tmp_path, trace=True)
    assert line["correct"], line["checks"]
    assert {"silo_upload_ms", "round_close_ms.silo", "store_read_ms.silo",
            "engine_fold_ms.silo", "compiles_in_window.silo"} <= \
        set(line["metrics"])
    assert line["metrics"]["compiles_in_window.silo"]["value"] == 0
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]


def test_bf16_rounds_are_checked_as_each_ends(tmp_path, monkeypatch):
    # each compared with the reference before the next is
    # submitted, keeping only its (gap, scale)
    line, run = rehearsal.rehearse_keeping_run(
        CELL, tmp_path, monkeypatch, payload=rehearsal.BF16)
    assert line["correct"], line["checks"]
    assert rehearsal.checked_as_each_ended(run)


def test_bf16_fault_is_caught_as_each_round_ends(tmp_path, monkeypatch):
    rehearsal.plant(monkeypatch, "answer_altered")
    line, run = rehearsal.rehearse_keeping_run(
        CELL, tmp_path, monkeypatch, payload=rehearsal.BF16)
    assert not line["correct"], line["checks"]
    assert rehearsal.checked_as_each_ended(run)
