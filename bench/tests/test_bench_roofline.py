"""The kernels' byte counts against a hand count, and the share."""
import pytest

from bench import harness, roofline

V5E = harness.load_json(harness.BENCH / "peaks.json")["devices"]["TPU v5 lite"]


def test_wsum_bytes_by_hand():
    # one VGG16 row: read 528 MB and 1 weight, write 528 MB of sums
    assert roofline.wsum_bytes(1, 132_000_000, 4) == \
        528_000_000 + 4 + 528_000_000
    assert roofline.wsum_bytes(3, 10, 2) == 60 + 12 + 40


def test_dequant_bytes_by_hand():
    # 58 CNN4.6 rows: 1,150,976 int8 codes and 562 fp32 scales each,
    # 58 fp32 weights, 1,150,976 fp32 sums written
    assert roofline.wsum_dequant_bytes(58, 1_150_976, 562) == \
        58 * 1_150_976 + 58 * 562 * 4 + 58 * 4 + 1_150_976 * 4


def test_step_bytes_reads_the_compiled_step():
    dense = [((1, 1000), "float32"), ((1,), "float32"), ((1000,), "float32"),
             ((), "float32")]
    quant = [((58, 4096), "int8"), ((58, 2), "float32"), ((58,), "float32"),
             ((4000,), "float32"), ((), "float32")]
    assert roofline.step_bytes("wsum", [dense, quant]) == 4000 + 4 + 4000
    assert roofline.step_bytes("dequant", [dense, quant]) == \
        58 * 4096 + 58 * 2 * 4 + 58 * 4 + 4096 * 4
    assert roofline.step_bytes("wsum", [quant]) is None
    assert roofline.step_bytes("wsum", [dense, [((2, 1000), "float32")]]) \
        is None   # two shapes: no single answer


def test_share_of_the_roofline():
    assert roofline.share_pct(819e9, 2.0, V5E) == pytest.approx(50.0)
    assert roofline.share_pct(819e9, 0.0, V5E) is None
    assert roofline.share_pct(819e9, 1.0, None) is None
