"""The trace reduction, on a profile recorded here on the CPU and on
hand-made intervals."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import tracing


def test_op_name_of_a_tpu_hlo_line():
    line = ("%weighted_sum_pallas.1 = f32[1,132000000]{1,0:T(1,128)} "
            "custom-call(f32[1,1]{1,0:T(1,128)} %bitcast.2), "
            'custom_call_target="tpu_custom_call"')
    assert tracing.op_name(line) == "weighted_sum_pallas.1"
    assert tracing.op_name("wrapped_reduce") == "wrapped_reduce"


def test_merge_and_covered():
    merged = tracing.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (6, 7)])
    assert merged == [(0, 2.5), (3, 4), (6, 7)]
    assert tracing.covered(merged, 1, 6.5) == pytest.approx(1.5 + 1 + 0.5)


def test_idle_gaps_are_labelled_by_the_innermost_span():
    red = tracing.Reduced(
        window=(0.0, 10.0), per_device=[[(1.0, 2.0), (6.0, 9.0)]],
        ops={"k.1": (2, 4.0)}, drift_s=0.0,
        spans=[("round_wait", 0.2, 9.2), ("fetch", 2.5, 5.0)])
    assert red.busy_s == 4.0 and red.window_s == 10.0
    gaps = red.idle_gaps()
    assert gaps[0] == ("fetch", 4.0)     # 2 .. 6: its middle is in both
    assert ("outside benchmark spans", 1.0) in gaps  # 9 .. 10
    assert red.busy_within([(0.0, 1.5), (8.0, 12.0)]) == 1.5
    assert red.op_time(lambda n: n.startswith("k")) == (2, 4.0)
    br = red.breakdown()
    assert br["device_ops"] == [["k.1", 4.0]]
    assert [g[0] for g in br["idle_gaps"]] == [
        "fetch", "round_wait", "outside benchmark spans"]


def test_reduce_a_recorded_cpu_profile(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    prof = tracing.Profile(str(tmp_path))
    prof.start()
    t0 = time.monotonic()
    with tracing.span("round_wait"):
        for _ in range(5):
            f(x).block_until_ready()
    t1 = time.monotonic()
    time.sleep(0.05)                     # idle, outside any span
    prof.stop()
    red = tracing.reduce(prof.path(), prof.marks)
    lo, hi = red.window
    assert lo <= t0 < t1 <= hi
    assert abs(red.drift_s) < 1e-3
    assert 0 < red.busy_s < red.window_s
    # the device work lies inside the span, on the monotonic clock
    assert red.busy_within([(t0 - 1e-3, t1 + 1e-3)]) == pytest.approx(
        red.busy_within([(lo, hi)]))
    names = [s[0] for s in red.spans]
    assert "round_wait" in names
    label, longest = red.idle_gaps()[0]
    assert label == "outside benchmark spans" and longest >= 0.04
    br = red.breakdown()
    assert 0 < len(br["device_ops"]) <= 10
    assert all(secs > 0 for _, secs in br["device_ops"])
