"""The float64 reference against hand-computed cases, and the limit
between an fp32 fold and the one-step-lower control."""
import hashlib
import tracemalloc

import numpy as np
import pytest

from bench import harness, payloads, reference, schedule

LIMIT = harness.LIMITS["max_rel_err"]


def _pool(config, rows):
    pool = reference.Pool(0, config, range(len(rows)))
    pool.rows = rows
    return pool


def test_dense_reference_by_hand():
    config = {"params": 3, "payload": {"kind": "float32"}}
    pool = _pool(config, [np.array([1, 2, 3], np.float32),
                          np.array([3, 0, -3], np.float32)])
    w = reference.round_weights(pool, [[(0, 1), (1, 3)], [(1, 2), (1, 2)]])
    np.testing.assert_array_equal(w, [[1, 3], [0, 4]])
    ref = (w @ pool.values64(0, 3)) / w.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(ref, [[2.5, 0.5, -1.5], [3, 0, -3]])
    fused = [np.array([2.5, 0.5, -1.5]), np.array([3, 0.3, -3])]
    errs = reference.rel_errors(pool, w, fused)
    assert errs[0] == 0.0
    assert errs[1] == pytest.approx(0.1)


def test_int8_reference_by_hand():
    config = {"params": 3, "payload": {"kind": "int8", "block": 2}}
    pool = _pool(config, [
        (np.array([1, -2, 3, 0], np.int8), np.array([0.5, 2.0], np.float32)),
        (np.array([2, 2, -1, 0], np.int8), np.array([1.0, 0.25], np.float32)),
    ])
    np.testing.assert_allclose(pool.values64(0, 3),
                               [[0.5, -1, 6], [2, 2, -0.25]])
    w = reference.round_weights(pool, [[(0, 1), (1, 1)]])
    ok = reference.rel_errors(pool, w, [np.array([1.25, 0.5, 2.875])])
    assert ok == [0.0]


@pytest.mark.parametrize("fused", [None, np.zeros(2), np.array([np.nan] * 3)])
def test_missing_or_broken_answers_read_inf(fused):
    config = {"params": 3, "payload": {"kind": "float32"}}
    pool = _pool(config, [np.array([1, 2, 3], np.float32)])
    w = reference.round_weights(pool, [[(0, 5)]])
    assert reference.rel_errors(pool, w, [fused]) == [float("inf")]


def _fp32_fold(pool, w):
    """FedAvg with fp32 products and sums: the precision the
    configurations state."""
    x = pool.values64(0, pool.config["params"]).astype(np.float32)
    acc = np.zeros(x.shape[1], np.float32)
    for i in range(x.shape[0]):
        acc += np.float32(w[0, i]) * x[i]
    return acc / np.float32(w[0].sum())


@pytest.mark.parametrize("config,n", [
    ({"params": 100_000, "payload": {"kind": "float32"},
      "sample_counts": [1000, 100000]}, 8),
    ({"params": 65_536, "payload": {"kind": "int8", "block": 2048},
      "sample_counts": [1, 100]}, 64),
])
def test_limit_separates_fp32_from_the_control(config, n):
    rng = np.random.default_rng(3)
    weights = rng.integers(*config["sample_counts"], size=n)
    rounds = [([(k, int(weights[k])) for k in range(n)], None)]
    pool = reference.Pool(11, config, range(n))
    w = reference.round_weights(pool, [rounds[0][0]])
    fp32 = reference.rel_errors(pool, w, [_fp32_fold(pool, w)])[0]
    control = reference.check_rounds(11, config, rounds,
                                     control=True)["max_rel_err"]
    assert fp32 < LIMIT < control


SEED = 2**31 + 12345
PINNED = {
    "float32": (
        {"params": 3000, "payload": {"kind": "float32"}, "tenants": 1,
         "clients_per_round": 8, "sample_counts": [1000, 100000]},
        [1.276711437824349e-07, 2.1023481184541935e-07,
         2.896311036840963e-07],
        [5.7302388558385e-06, 5.8491636289345285e-06,
         7.683595772925225e-06],
        "0d23f433847714e357d338e877609cbe05a51a734a3216c6b83d4129869ccdd4"),
    "int8": (
        {"params": 5000, "payload": {"kind": "int8", "block": 2048},
         "tenants": 4, "clients_per_round": 8, "sample_counts": [1, 100]},
        [1.1021041131746725e-07, 2.0820132254863944e-07,
         2.8997209270839955e-07],
        [3.0140817828611393e-06, 2.359888282709635e-06,
         2.191893521465585e-06],
        "e08bfafd340f548a1f2e99933794a4bb0bac334e2c0e1406c753d6247dc0b014"),
}


def _pinned_rounds(config):
    """Three rounds of a pool of 16, each answered by its reference
    scaled by 1 + (r + 1) * 1e-7."""
    rounds = []
    for r in range(3):
        ups = schedule.round_uploads(SEED, config, {"payload_pool": 16}, 0, r)
        inc = [(u.key, u.weight) for u in ups]
        pool = reference.Pool(SEED, config, [k for k, _ in inc])
        w = reference.round_weights(pool, [inc])
        ref = (w @ pool.values64(0, config["params"])) / w.sum()
        rounds.append((inc, (ref[0] * (1 + 1e-7 * (r + 1))).astype(
            np.float32)))
    return rounds


@pytest.mark.parametrize("kind", sorted(PINNED))
def test_reference_values_are_unchanged(kind):
    # what these seeds read before ranged payloads and the chunks' thread
    # pool were added
    config, errs, control, digest = PINNED[kind]
    rounds = _pinned_rounds(config)
    assert reference.check_rounds(SEED, config, rounds)["rel_errors"] == errs
    assert reference.check_rounds(SEED, config, rounds,
                                  control=True)["rel_errors"] == control
    pool = reference.Pool(SEED, config, range(16))
    w = reference.round_weights(pool, [inc for inc, _ in rounds])
    ref = (w @ pool.values64(0, config["params"])) / w.sum(axis=1,
                                                           keepdims=True)
    assert hashlib.sha256(ref.tobytes()).hexdigest() == digest


BF16 = {"params": 100_003, "payload": {"kind": "bfloat16"}, "tenants": 1,
        "sample_counts": [1000, 100000]}


def test_bf16_reference_reads_ranges_by_hand():
    pool = reference.Pool(SEED, BF16, [1, 0, 1])
    assert pool.rows is None and pool.keys == [0, 1]
    lo, hi = 65_530, 65_542      # across a tile's edge
    x = np.stack([payloads.make(SEED, BF16, k)[lo:hi] for k in (0, 1)])
    np.testing.assert_array_equal(pool.values64(lo, hi),
                                  x.astype(np.float64))
    assert list(pool.chunks()) == [(0, BF16["params"])]


@pytest.mark.parametrize("weights", [
    [70_001, 1_000],
    [99_999, 65_537, 12_345],
])
def test_limit_separates_bf16_from_the_control(weights):
    rounds = [([(k, w) for k, w in enumerate(weights)], None)]
    pool = reference.Pool(11, BF16, range(len(weights)))
    w = reference.round_weights(pool, [rounds[0][0]])
    fp32 = reference.rel_errors(pool, w, [_fp32_fold(pool, w)])[0]
    control = reference.check_rounds(11, BF16, rounds,
                                     control=True)["max_rel_err"]
    assert fp32 < LIMIT < control


def test_high_products_lose_nothing_on_bf16_values():
    # why the bf16 control steps down the values and not the products:
    # at Precision.HIGH a count under 2**17 splits exactly into two
    # bfloat16 parts, so every product of a bf16 value is exact
    weights = np.array([[99_999, 65_537, 131_071]], np.float32)
    wh, wl = reference._bf16_parts(weights)
    np.testing.assert_array_equal(wh + wl, weights)
    pool = reference.Pool(11, BF16, range(3))
    x = pool.dense(0, BF16["params"]).astype(np.float32)
    high = (wh @ x + wl @ x) / weights.sum()
    assert reference.rel_errors(pool, weights.astype(np.float64),
                                [high[0]])[0] < LIMIT


@pytest.mark.parametrize("config,pool_size", [
    (dict(BF16, params=3 * payloads.TILE + 5, clients_per_round=3), 2),
    (dict(PINNED["int8"][0], params=70_000), 16),
])
def test_each_round_and_after_window_agree(config, pool_size):
    traffic = {"payload_pool": pool_size}
    rounds = []
    for r in range(4):
        ups = schedule.round_uploads(SEED, config, traffic, 0, r)
        fused = np.random.default_rng(r).standard_normal(
            config["params"]).astype(np.float32)
        rounds.append(([(u.key, u.weight) for u in ups], fused))
    after = reference.check_rounds(SEED, config, rounds)["rel_errors"]
    each = [reference.rel_error(*reference.check_round(SEED, config, inc, f))
            for inc, f in rounds]
    assert each == after


def test_pool_smaller_than_a_round_still_matches():
    config = dict(BF16, params=5_000, clients_per_round=8)
    ups = schedule.round_uploads(SEED, config, {"payload_pool": 2}, 0, 0)
    inc = [(u.key, u.weight) for u in ups]
    rows = [payloads.make(SEED, config, k).astype(np.float64) for k in (0, 1)]
    by_hand = sum(w * rows[k] for k, w in inc) / sum(w for _, w in inc)
    gap, scale = reference.check_round(SEED, config, inc,
                                       by_hand.astype(np.float32))
    assert reference.rel_error(gap, scale) < 1e-7


def test_ranged_check_holds_no_whole_row(monkeypatch):
    config = dict(BF16, params=32 * payloads.TILE)
    made = []
    make_range = payloads.make_range
    monkeypatch.setattr(reference, "_RANGE_CHUNK", payloads.TILE)
    monkeypatch.setattr(payloads, "workers", lambda: 1)
    monkeypatch.setattr(payloads, "make", None)   # no whole row
    monkeypatch.setattr(payloads, "make_range", lambda s, c, k, lo, hi: (
        made.append(hi - lo) or make_range(s, c, k, lo, hi)))
    inc = [(0, 70_001), (1, 1_000), (0, 5)]
    fused = np.zeros(config["params"], np.float32)
    tracemalloc.start()
    try:
        gap, scale = reference.check_round(SEED, config, inc, fused)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert reference.rel_error(gap, scale) == pytest.approx(1.0)
    assert max(made) == payloads.TILE and len(made) == 2 * 32
    # a chunk's values and arithmetic, under one bf16 row
    assert peak < 2 * config["params"]


def _run_of(config, rounds):
    """A run of one tenant whose window rounds answered ``rounds``, each
    ``(included (key, weight) pairs, fused)``."""
    cell = harness.Cell(name="bf16", chips=1, config=config, traffic={},
                        driver_path=None, end_to_end=[], per_layer=[])
    ctx = harness.Context(cell=cell, seed=SEED, seconds=1.0, trace=False,
                          started=0.0)
    uploads, recs = [], []
    for r, (inc, fused) in enumerate(rounds):
        cids = [f"r{r}c{i}" for i in range(len(inc))]
        uploads += [harness.UploadRec(cid=c, tenant="t0", key=k, weight=w,
                                      due=None, sent=0.5, acked=0.6)
                    for c, (k, w) in zip(cids, inc)]
        recs.append(harness.RoundRec(tenant="t0", started=0.5, on_host=0.9,
                                     included=cids, n_clients=len(cids),
                                     fused=fused))
    return harness.Run(ctx=ctx, setup_s=0.0, window=(0.0, 1.0),
                       uploads=uploads, rounds=recs, counters={},
                       leftover={"t0": []})


@pytest.mark.parametrize("r", range(3))
def test_default_products_on_bf16_are_not_correct(r):
    # the bf16 fold's likeliest slip, a single bf16 pass that rounds the
    # sample counts, put in the program's place: the limit fails it
    config = dict(BF16, clients_per_round=3)
    ups = schedule.round_uploads(SEED, config, {"payload_pool": 2}, 0, r)
    inc = [(u.key, u.weight) for u in ups]
    pool = reference.Pool(SEED, config, [k for k, _ in inc])
    w = reference.round_weights(pool, [inc])
    default = reference.default_chunk(pool, w, 0, config["params"])[0]
    sound = harness.checks(_run_of(config, [(inc, _fp32_fold(pool, w))]))
    slip = harness.checks(_run_of(config, [(inc, default)]))
    assert harness.correct(sound) and not harness.correct(slip)
    assert sound["max_rel_err"] < LIMIT < slip["max_rel_err"]
