"""The float64 reference against hand-computed cases, and the limit
between an fp32 fold and the one-step-lower control."""
import numpy as np
import pytest

from bench import harness, reference

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
