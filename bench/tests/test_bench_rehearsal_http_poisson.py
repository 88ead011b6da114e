"""xdev_cnn46_int8.http_poisson rehearsed on the CPU at a tiny size: correct as it stands,
and not correct with a fault planted under the timed path."""
import pytest

from bench.tests import rehearsal

CELL = "xdev_cnn46_int8.http_poisson"


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
    assert {"upload_tail_p99_ms", "gen_lag_p99_ms", "ingest_batch_mean",
            "round_close_ms.poisson", "store_read_ms.poisson",
            "engine_fold_ms.poisson", "compiles_in_window.poisson"} <= \
        set(line["metrics"])
    assert line["metrics"]["compiles_in_window.poisson"]["value"] == 0
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]


def test_bf16_rounds_are_refused(tmp_path):
    # its uploads are recorded only at the run's end, so it cannot check
    # a round as it ends: refused before anything starts
    with pytest.raises(ValueError, match="checked as each ends"):
        rehearsal.rehearse(CELL, tmp_path, payload=rehearsal.BF16)
