"""The payload generators and the schedules depend on the seed alone."""
import numpy as np
import pytest

from bench import payloads, schedule

INT8 = {"params": 5000, "payload": {"kind": "int8", "block": 2048},
        "tenants": 4, "clients_per_round": 8, "sample_counts": [1, 100]}
DENSE = {"params": 3000, "payload": {"kind": "float32"}, "tenants": 1,
         "clients_per_round": 8, "sample_counts": [1000, 100000]}
OPEN = {"rate_per_s": 240, "epoch_s": 1.0, "payload_pool": 64}
SEED = 2**31 + 12345   # seeds may exceed 32 bits


def test_dense_payload_is_seeded():
    a = payloads.make(SEED, DENSE, 3)
    assert a.dtype == np.float32 and a.shape == (3000,)
    np.testing.assert_array_equal(a, payloads.make(SEED, DENSE, 3))
    assert not np.array_equal(a, payloads.make(SEED, DENSE, 4))
    assert not np.array_equal(a, payloads.make(SEED + 1, DENSE, 3))


def test_int8_payload_is_seeded_and_well_formed():
    codes, scales = payloads.make(SEED, INT8, 7)
    assert codes.dtype == np.int8 and codes.shape == (3 * 2048,)
    assert scales.dtype == np.float32 and scales.shape == (3,)
    assert codes.min() >= -127 and not codes[5000:].any()
    assert (scales > 0).all() and np.isfinite(scales).all()
    again = payloads.make(SEED, INT8, 7)
    np.testing.assert_array_equal(codes, again[0])
    np.testing.assert_array_equal(scales, again[1])


def test_open_loop_epoch_is_seeded_with_fixed_work():
    ups = schedule.open_loop_epoch(SEED, INT8, OPEN, 5)
    assert ups == schedule.open_loop_epoch(SEED, INT8, OPEN, 5)
    assert ups != schedule.open_loop_epoch(SEED + 1, INT8, OPEN, 5)
    # every seed offers the same number of uploads, tenants share it
    # evenly, and every arrival falls inside its epoch in order
    for seed in (SEED, 7, 0):
        e = schedule.open_loop_epoch(seed, INT8, OPEN, 5)
        assert len(e) == 240
        due = [u.due for u in e]
        assert due == sorted(due) and 5.0 <= due[0] and due[-1] < 6.0
        counts = {t: sum(u.tenant == t for u in e)
                  for t in schedule.tenant_names(INT8)}
        assert set(counts.values()) == {60}
    ids = [u.cid for k in range(3)
           for u in schedule.open_loop_epoch(SEED, INT8, OPEN, k)]
    assert len(set(ids)) == len(ids)


def test_open_loop_gaps_look_poisson():
    gaps = np.diff([u.due for k in range(20)
                    for u in schedule.open_loop_epoch(SEED, INT8, OPEN, k)])
    # exponential gaps: mean 1/rate, coefficient of variation about 1
    assert gaps.mean() == pytest.approx(1 / 240, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


@pytest.mark.parametrize("config,traffic", [(DENSE, {}),
                                            (INT8, {"payload_pool": 64})])
def test_round_uploads_are_seeded(config, traffic):
    a = schedule.round_uploads(SEED, config, traffic, 0, 3)
    assert a == schedule.round_uploads(SEED, config, traffic, 0, 3)
    assert a != schedule.round_uploads(SEED, config, traffic, 0, 4)
    keys = [u.key for u in a]
    assert len(a) == 8 and len(set(keys)) == 8
    lo, hi = config["sample_counts"]
    assert all(lo <= u.weight <= hi for u in a)
    later = schedule.round_uploads(SEED, config, traffic, 0, 4)
    assert not {u.cid for u in a} & {u.cid for u in later}
