"""The payload generators and the schedules depend on the seed alone."""
import hashlib
import tracemalloc

import ml_dtypes
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


BF16 = {"params": 3 * payloads.TILE + 123, "payload": {"kind": "bfloat16"},
        "tenants": 1, "clients_per_round": 3, "sample_counts": [1000, 100000]}
T = payloads.TILE


def _sha(*arrays) -> str:
    return hashlib.sha256(b"".join(np.ascontiguousarray(a).tobytes()
                                   for a in arrays)).hexdigest()


@pytest.mark.parametrize("config,key,digest", [
    (DENSE, 3,
     "2fe5cb8a21750d66f8697af050eee66f6b70da776d554af6d435a4b2bf5f4f83"),
    (INT8, 7,
     "5aed2f08fcc942ea929ebddd962c7a39b2b7ad2d172e15c0afd446f1a29d0290"),
])
def test_whole_payloads_keep_their_bytes(config, key, digest):
    # the bytes these seeds gave before bf16 payloads were added
    made = payloads.make(SEED, config, key)
    assert _sha(*(made if isinstance(made, tuple) else (made,))) == digest


def test_bf16_payload_is_seeded():
    a = payloads.make(SEED, BF16, 1)
    assert a.dtype == ml_dtypes.bfloat16 and a.shape == (BF16["params"],)
    np.testing.assert_array_equal(a, payloads.make(SEED, BF16, 1))
    assert not np.array_equal(a, payloads.make(SEED, BF16, 0))
    assert not np.array_equal(a, payloads.make(SEED + 1, BF16, 1))
    assert np.isfinite(a.astype(np.float32)).all()
    assert 0.9 < a.astype(np.float64).std() < 1.1


@pytest.mark.parametrize("lo,hi", [
    (0, 1), (5, 5), (T - 5, T + 7), (T, 2 * T), (T + 1, 3 * T - 1),
    (0, 3 * T + 123), (3 * T, 3 * T + 123), (3 * T + 122, 3 * T + 123)])
def test_make_range_is_the_slice_of_make(lo, hi):
    part = payloads.make_range(SEED, BF16, 2, lo, hi)
    assert part.dtype == ml_dtypes.bfloat16 and part.shape == (hi - lo,)
    np.testing.assert_array_equal(part, payloads.make(SEED, BF16, 2)[lo:hi])


def test_make_range_refuses_what_it_cannot_make():
    with pytest.raises(ValueError):
        payloads.make_range(SEED, BF16, 0, 0, BF16["params"] + 1)
    with pytest.raises(ValueError):
        payloads.make_range(SEED, DENSE, 0, 0, 10)


def test_bf16_row_has_no_whole_fp32_copy():
    config = dict(BF16, params=64 * T)
    tracemalloc.start()
    try:
        row = payloads.make(SEED, config, 0)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # the bf16 row and one fp32 tile per thread; a whole fp32 row
    # alone would be twice the bf16 row
    assert row.nbytes <= peak < row.nbytes + payloads.workers() * 8 * T
    assert peak < 4 * config["params"]


def test_round_keys_from_the_pool_are_unchanged():
    # the draw these seeds gave before small pools were allowed
    ups = schedule.round_uploads(SEED, INT8, {"payload_pool": 16}, 0, 1)
    assert [u.key for u in ups] == [11, 0, 5, 6, 10, 1, 12, 4]


def test_pool_smaller_than_a_round_reuses_keys():
    config = dict(DENSE, clients_per_round=8)
    a = schedule.round_uploads(SEED, config, {"payload_pool": 2}, 0, 3)
    assert a == schedule.round_uploads(SEED, config, {"payload_pool": 2},
                                       0, 3)
    keys = [u.key for u in a]
    assert len(a) == 8 and set(keys) == {0, 1}
    assert len({u.cid for u in a}) == 8
