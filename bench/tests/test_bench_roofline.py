"""The kernels' byte counts against a hand count, and the share."""
import types

import pytest

from bench import harness, roofline, tracing

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


STEP = [[((8, 1_000_000), "bfloat16"), ((8,), "float32"),
         ((1_000_000,), "float32")]]


def _traced(per_device_calls, secs_per_call):
    """A run whose trace holds the weighted-sum kernel's calls on each
    device, one call a fold step."""
    devices = len(per_device_calls)
    calls = sum(per_device_calls)
    per_device = [[(float(i), i + secs_per_call) for i in range(n)]
                  for n in per_device_calls]
    trace = tracing.Reduced(
        window=(0.0, 100.0), per_device=per_device,
        ops={"weighted_sum_pallas.3": (calls, calls * secs_per_call)},
        spans=[], drift_s=0.0)
    assert len(trace.per_device) == devices
    return types.SimpleNamespace(trace=trace, fold_steps=STEP, peaks=V5E)


def test_kernel_share_is_the_same_split_over_two_devices():
    # four steps of a global (8, 1e6) bf16 operand: on one chip 1 ms a
    # call; on two, each chip folds half of every step in 0.5 ms
    one = roofline.kernel_share(_traced([4], 1e-3), "wsum",
                                "weighted_sum_pallas")
    two = roofline.kernel_share(_traced([4, 4], 0.5e-3), "wsum",
                                "weighted_sum_pallas")
    assert one == pytest.approx(two, rel=1e-12)
    per_step = roofline.wsum_bytes(8, 1_000_000, 2)
    assert one == pytest.approx(100.0 * 4 * per_step / 819e9 / 4e-3)


def test_kernel_share_on_one_device_is_the_calls_times_the_step():
    # what the readers computed before the mesh: bit for bit
    run = _traced([7], 1.25e-3)
    calls, secs = run.trace.op_time(lambda n: n.startswith("weighted"))
    assert roofline.kernel_share(run, "wsum", "weighted_sum_pallas") == \
        roofline.share_pct(calls * roofline.step_bytes("wsum", STEP), secs,
                           V5E)


def test_kernel_share_reads_nothing_without_a_call():
    run = _traced([3], 1e-3)
    assert roofline.kernel_share(run, "dequant",
                                 "weighted_sum_dequant_pallas") is None
    run.trace = None
    assert roofline.kernel_share(run, "wsum", "weighted_sum_pallas") is None
