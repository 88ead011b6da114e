"""Streamed robust aggregation (PR 7): the order-statistic reducers
(TrimmedMean / CoordMedian) stream off the store through the per-
coordinate top-k/bottom-k carve, matching the dense oracles:

  * carve stream == dense sort at chunk 1 / odd / pow2 and ragged final
    blocks, both engine strategies and the distributed mesh;
  * mixed compressed + dense rounds fold through the same carve (the
    dequant runs in-trace, so the order statistics match a host-side
    dequant exactly);
  * the TrimmedMean over-trim NaN regression (2*int(n*beta) >= n) is
    clamped to (n-1)//2;
  * Zeno's validation gradient is per-call state, safe across two
    concurrent tenants;
  * the service's state budget routes huge carve rounds dense with a
    RoundReport note (covered in test_streaming / test_async_rounds).
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AggregationService, LocalEngine, UpdateStore
from repro.core.fusion import get_fusion
from repro.core.fusion.robust import CoordMedian, TrimmedMean, Zeno
from repro.kernels.robust_fusion.ops import carve_stream_dense
from repro.kernels.robust_fusion.ref import coordmedian_ref, trimmedmean_ref

RNG = np.random.default_rng(7)


def _blocks(u, w, chunk):
    for lo in range(0, u.shape[0], chunk):
        yield u[lo:lo + chunk], w[lo:lo + chunk]


def _oracle(fusion, u):
    if fusion.name == "coordmedian":
        return np.asarray(coordmedian_ref(jnp.asarray(u)))
    return np.asarray(
        trimmedmean_ref(jnp.asarray(u), fusion.trim_count(u.shape[0]))
    )


# -- streamed carve == dense oracle -------------------------------------------


@pytest.mark.parametrize("fusion", [CoordMedian(), TrimmedMean(beta=0.2)])
@pytest.mark.parametrize("strategy", ["jnp", "pallas"])
@pytest.mark.parametrize("n,p,chunk", [
    (9, 257, 1),     # chunk 1: every row is its own fold
    (13, 301, 3),    # odd chunk, ragged final block
    (16, 64, 8),     # pow2, exact blocks
])
def test_carve_stream_matches_dense(fusion, strategy, n, p, chunk):
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = np.ones((n,), np.float32)
    eng = LocalEngine(strategy=strategy)
    streamed, rep = eng.fuse_stream(
        fusion, _blocks(u, w, chunk), chunk_rows=chunk, n_hint=n
    )
    np.testing.assert_allclose(
        np.asarray(streamed), _oracle(fusion, u), rtol=1e-5, atol=1e-5
    )
    assert rep.n_rows == n
    assert rep.acc_state is not None and len(rep.acc_state) == 4


def test_carve_stream_dense_harness_matches_refs():
    u = jnp.asarray(RNG.normal(size=(11, 130)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(carve_stream_dense(u, 2, chunk=3, interpret=True)),
        np.asarray(trimmedmean_ref(u, 2)), rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(  # trim (11-1)//2: the median
        np.asarray(carve_stream_dense(u, 5, chunk=4, interpret=True)),
        np.asarray(coordmedian_ref(u)), rtol=1e-5, atol=1e-5,
    )


def test_carve_stream_ignores_client_weights():
    """Order statistics are unweighted: arbitrary store weights must not
    change the fold (the engine only uses row validity)."""
    n, p = 10, 65
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = RNG.uniform(0.1, 9.0, size=(n,)).astype(np.float32)
    fused, _ = LocalEngine().fuse_stream(
        CoordMedian(), _blocks(u, w, 4), chunk_rows=4, n_hint=n
    )
    np.testing.assert_allclose(
        np.asarray(fused), np.median(u, axis=0), rtol=1e-5, atol=1e-5
    )


def test_carve_stream_rejects_staleness_scale():
    n, p = 6, 16
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = np.ones((n,), np.float32)

    def blocks():
        yield u[:3], w[:3], np.full((3,), 0.5, np.float32)
        yield u[3:], w[3:], np.full((3,), 0.5, np.float32)

    with pytest.raises(ValueError, match="staleness"):
        LocalEngine().fuse_stream(TrimmedMean(), blocks(), chunk_rows=3,
                                  n_hint=n)


def test_service_streamed_trimmedmean_sync_and_async():
    """The acceptance path: AggregationService(fusion=TrimmedMean)
    streams a store round — sync and async — to the dense oracle."""
    n, p = 12, 512
    u = RNG.normal(size=(n, p)).astype(np.float32)
    fusion = TrimmedMean(beta=0.2)
    oracle = _oracle(fusion, u)
    for async_round in (False, True):
        store = UpdateStore()
        for i in range(n):
            store.write(f"c{i}", u[i])
        svc = AggregationService(fusion=TrimmedMean(beta=0.2), store=store,
                                 monitor_timeout=1.0,
                                 stream_chunk_bytes=4 * p * 5)
        fused, rep = svc.aggregate(from_store=True, expected_clients=n,
                                   async_round=async_round)
        assert rep.streamed
        assert rep.async_round == async_round
        np.testing.assert_allclose(np.asarray(fused), oracle,
                                   rtol=1e-5, atol=1e-5)


def test_service_streamed_carve_reuses_warm_step():
    """A second same-shape round must reuse the carve step executable."""
    from repro.utils import jitcache

    n, p = 8, 128
    store = UpdateStore()
    svc = AggregationService(fusion=TrimmedMean(beta=0.2), store=store,
                             monitor_timeout=0.5,
                             stream_chunk_bytes=4 * p * 3)
    for rnd in range(2):
        u = RNG.normal(size=(n, p)).astype(np.float32)
        for i in range(n):
            store.write(f"c{i}", u[i])
        if rnd == 1:
            before = jitcache.trace_count()
        fused, rep = svc.aggregate(from_store=True, expected_clients=n)
        assert rep.streamed
        np.testing.assert_allclose(
            np.asarray(fused),
            _oracle(TrimmedMean(beta=0.2), u), rtol=1e-5, atol=1e-5,
        )
        store.clear()
    assert jitcache.trace_count() == before, "warm carve round re-traced"
    assert rep.phase_seconds["compile"] == 0.0


def test_service_mixed_compressed_dense_carve_round():
    """Stragglers may write uncompressed fp32 into a compressed round;
    the carve folds both payload kinds. Oracle: host-side dequant of the
    compressed rows (in-trace dequant is bit-identical)."""
    n, p = 10, 200
    u = RNG.normal(size=(n, p)).astype(np.float32)
    store = UpdateStore()
    svc = AggregationService(fusion=TrimmedMean(beta=0.2), store=store,
                             monitor_timeout=0.5, compress=True)
    mixed = np.empty_like(u)
    for i in range(n):
        if i % 3 == 0:   # straggler: dense fp32
            store.write(f"c{i}", u[i])
            mixed[i] = u[i]
        else:
            cu = svc.compress_update(f"c{i}", u[i])
            store.write(f"c{i}", cu)
            mixed[i] = cu.dequantize()[:p]
    fused, rep = svc.aggregate(from_store=True, expected_clients=n)
    assert rep.streamed
    np.testing.assert_allclose(
        np.asarray(fused), _oracle(TrimmedMean(beta=0.2), mixed),
        rtol=1e-5, atol=1e-5,
    )


# -- TrimmedMean over-trim regression (satellite a) ---------------------------


@pytest.mark.parametrize("n,beta", [(4, 0.5), (5, 0.5), (3, 0.4), (2, 0.5)])
def test_trimmedmean_over_trim_clamps_instead_of_nan(n, beta):
    """2*int(n*beta) >= n used to divide by zero (NaN fused model); the
    trim count now clamps to (n-1)//2."""
    u = RNG.normal(size=(n, 33)).astype(np.float32)
    f = TrimmedMean(beta=beta)
    k = f.trim_count(n)
    assert 2 * k < n
    dense = np.asarray(f.fuse(jnp.asarray(u), jnp.ones((n,))))
    assert np.isfinite(dense).all()
    np.testing.assert_allclose(
        dense, np.asarray(trimmedmean_ref(jnp.asarray(u), k)),
        rtol=1e-5, atol=1e-6,
    )
    streamed, _ = LocalEngine().fuse_stream(
        f, _blocks(u, np.ones((n,), np.float32), 2), chunk_rows=2, n_hint=n
    )
    np.testing.assert_allclose(np.asarray(streamed), dense,
                               rtol=1e-5, atol=1e-5)


# -- Zeno per-call validation gradient (satellite b) --------------------------


def test_zeno_val_grad_is_per_call_state():
    """Two tenants scoring against DIFFERENT validation gradients on one
    shared service must not race one fusion's _g_val."""
    n, p = 6, 64
    u = RNG.normal(size=(n, p)).astype(np.float32)
    g1 = np.ones((p,), np.float32)
    g2 = -np.ones((p,), np.float32)
    base = Zeno()
    ref1 = np.asarray(base.with_val_grad(g1).fuse(jnp.asarray(u),
                                                  jnp.ones((n,))))
    ref2 = np.asarray(base.with_val_grad(g2).fuse(jnp.asarray(u),
                                                  jnp.ones((n,))))
    assert base._g_val is None   # clone, not mutation
    assert not np.allclose(ref1, ref2)

    svc = AggregationService(fusion="zeno")
    results = {}
    errors = []

    def round_for(tenant, g, ref):
        try:
            fused, _ = svc.aggregate(updates=[r for r in u], val_grad=g,
                                     tenant=tenant)
            results[tenant] = (np.asarray(fused), ref)
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    ts = [threading.Thread(target=round_for, args=("a", g1, ref1)),
          threading.Thread(target=round_for, args=("b", g2, ref2))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
    for tenant, (fused, ref) in results.items():
        np.testing.assert_allclose(fused, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"tenant {tenant}")
    assert svc.fusion._g_val is None


def test_zeno_set_val_grad_still_works():
    """The legacy mutating setter stays for single-tenant callers."""
    n, p = 5, 32
    u = jnp.asarray(RNG.normal(size=(n, p)).astype(np.float32))
    g = jnp.ones((p,))
    f = Zeno()
    f.set_val_grad(g)
    np.testing.assert_allclose(
        np.asarray(f.fuse(u, jnp.ones((n,)))),
        np.asarray(Zeno().with_val_grad(g).fuse(u, jnp.ones((n,)))),
        rtol=1e-6, atol=1e-7,
    )


# -- carve state carry across streams -----------------------------------------


def test_carve_acc_state_resumes_stream():
    """acc_state from a closed stream seeds a second stream; the result
    equals one pass over the concatenated rows (async carry-over)."""
    n1, n2, p = 6, 5, 90
    u1 = RNG.normal(size=(n1, p)).astype(np.float32)
    u2 = RNG.normal(size=(n2, p)).astype(np.float32)
    n = n1 + n2
    f = CoordMedian()
    eng = LocalEngine()
    _, rep1 = eng.fuse_stream(
        f, _blocks(u1, np.ones((n1,), np.float32), 3),
        chunk_rows=3, n_hint=n,
    )
    fused, rep2 = eng.fuse_stream(
        f, _blocks(u2, np.ones((n2,), np.float32), 3),
        init=rep1.acc_state, chunk_rows=3, n_hint=n,
    )
    np.testing.assert_allclose(
        np.asarray(fused), np.median(np.vstack([u1, u2]), axis=0),
        rtol=1e-5, atol=1e-5,
    )
    assert rep2.n_rows == n2


def test_carve_rejects_staleness_discount_service():
    with pytest.raises(ValueError, match="weighted"):
        AggregationService(fusion="trimmedmean", staleness_discount=0.9)


def test_coordmedian_large_n_state_signature_scales():
    """K grows with n for the median: the state signature (and so the
    compile-cache key) must depend on n_hint."""
    f = CoordMedian()
    assert f.state_signature(100, 5) != f.state_signature(100, 50)
    assert f.state_nbytes(100, 51) > f.state_nbytes(100, 5)
    with pytest.raises(ValueError, match="n_hint"):
        f.init_state(100, None)
