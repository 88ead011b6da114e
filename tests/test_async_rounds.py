"""Monitor-overlapped async rounds: gate edge cases (deterministic
injected clock), arrival-driven store iteration, queue/staleness
semantics, and the store/service correctness fixes that ride along:

  * timed-out round on an empty store returns a structured empty report
    (no LookupError out of ``store.meta()``);
  * ``UpdateStore.clear()`` resets stats and deletes spool blobs outside
    the lock; ``remove()`` consumes; memory-backend ``read()`` hands out
    immutable views;
  * distributed rounds surface a ``compile`` phase (cold vs warm).
"""
import bisect
import os

import numpy as np
import pytest

from repro.core import (
    AggregationService,
    DistributedEngine,
    LocalEngine,
    Monitor,
    Planner,
    UpdateStore,
    Workload,
    get_fusion,
)
from repro.core.compress import stack_block
from repro.launch.mesh import make_mesh

RNG = np.random.default_rng(31)


class ScriptedClock:
    def __init__(self):
        self.t = 0.0
        self._events = []

    def at(self, t, fn):
        bisect.insort(self._events, (t, id(fn), fn))

    def clock(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds
        while self._events and self._events[0][0] <= self.t:
            _, _, fn = self._events.pop(0)
            fn()


def _mk(n, p=64):
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
    return u, w


def _fedavg(u, w):
    return np.einsum("np,n->p", u, w) / (w.sum() + 1e-6)


def _service(store, clk, **kw):
    kw.setdefault("threshold_frac", 1.0)
    return AggregationService(
        fusion="fedavg", local_strategy="jnp", store=store,
        clock=clk.clock, sleep=clk.sleep, **kw,
    )


# -- monitor gate edge cases ---------------------------------------------------


def test_async_timeout_zero_arrivals_empty_report():
    clk = ScriptedClock()
    store = UpdateStore()
    svc = _service(store, clk, monitor_timeout=1.0)
    fused, rep = svc.aggregate(from_store=True, expected_clients=5,
                               async_round=True)
    assert fused is None and rep.empty and rep.async_round
    assert not rep.monitor.ready and rep.monitor.count == 0
    assert rep.monitor.waited >= 1.0
    assert rep.n_clients == 0 and rep.fuse_seconds == 0.0


def test_sync_timeout_empty_store_no_crash():
    """The satellite bug verbatim: serialized store round, empty store,
    monitor times out -> structured report, not LookupError."""
    clk = ScriptedClock()
    svc = _service(UpdateStore(), clk, monitor_timeout=0.5)
    fused, rep = svc.aggregate(from_store=True)
    assert fused is None and rep.empty and not rep.async_round
    assert rep.monitor is not None and not rep.monitor.ready


def test_async_timeout_partial_arrivals():
    """3 of 8 land before the deadline: the round folds exactly those 3
    and reports ready=False."""
    n, p = 8, 96
    u, w = _mk(n, p)
    clk = ScriptedClock()
    store = UpdateStore()
    for i in range(3):
        clk.at(0.2 * (i + 1),
               lambda i=i: store.write(f"c{i}", u[i], weight=float(w[i])))
    # clients 3..7 never arrive
    svc = _service(store, clk, monitor_timeout=2.0)
    fused, rep = svc.aggregate(from_store=True, expected_clients=n,
                               async_round=True)
    assert not rep.monitor.ready and rep.monitor.count == 3
    assert rep.n_clients == 3
    np.testing.assert_allclose(
        np.asarray(fused), _fedavg(u[:3], w[:3]), rtol=1e-4, atol=1e-5
    )


def test_threshold_reached_exactly_at_timeout():
    """The last required update lands at t == timeout: threshold wins the
    tie — the round is ready, not timed out (both for Monitor.wait and
    the async gate)."""
    n, p = 4, 32
    u, w = _mk(n, p)
    timeout = 1.0

    clk = ScriptedClock()
    store = UpdateStore()
    mon = Monitor(store, threshold=n, timeout=timeout, poll_interval=0.1,
                  clock=clk.clock, sleep=clk.sleep)
    for i in range(n - 1):
        clk.at(0.2, lambda i=i: store.write(f"c{i}", u[i],
                                            weight=float(w[i])))
    clk.at(timeout, lambda: store.write(f"c{n-1}", u[n - 1],
                                        weight=float(w[n - 1])))
    res = mon.wait()
    assert res.ready and res.count == n and res.waited >= timeout

    clk2 = ScriptedClock()
    store2 = UpdateStore()
    for i in range(n - 1):
        clk2.at(0.2, lambda i=i: store2.write(f"c{i}", u[i],
                                              weight=float(w[i])))
    clk2.at(timeout, lambda: store2.write(f"c{n-1}", u[n - 1],
                                          weight=float(w[n - 1])))
    svc = _service(store2, clk2, monitor_timeout=timeout)
    fused, rep = svc.aggregate(from_store=True, expected_clients=n,
                               async_round=True)
    assert rep.monitor.ready and rep.n_clients == n
    np.testing.assert_allclose(np.asarray(fused), _fedavg(u, w),
                               rtol=1e-4, atol=1e-5)


def test_late_writes_land_during_inflight_stream():
    """Writes scheduled AFTER the stream opens are picked up by the live
    iterator (no up-front snapshot) and fold into the same round."""
    n, p, chunk = 9, 40, 2
    u, w = _mk(n, p)
    clk = ScriptedClock()
    store = UpdateStore()
    # two present at the start, the rest trickle in while in-flight
    for i in range(2):
        store.write(f"c{i:02d}", u[i], weight=float(w[i]))
    for i in range(2, n):
        clk.at(0.1 * i, lambda i=i: store.write(f"c{i:02d}", u[i],
                                                weight=float(w[i])))
    seen_counts = []

    def gate(count, waited):
        seen_counts.append(count)
        return count >= n or waited >= 5.0

    got = list(store.iter_arrivals(
        chunk, gate, poll_interval=0.05, clock=clk.clock, sleep=clk.sleep,
    ))
    assert sum(b.shape[0] for b, _, _ in got) == n
    # only the FINAL block may be ragged (fixed-shape step executables)
    assert all(b.shape[0] == chunk for b, _, _ in got[:-1])
    # the stream saw the count GROW while in flight: arrival-driven
    assert seen_counts[0] < n and max(seen_counts) == n
    stacked = np.concatenate([stack_block(b) for b, _, _ in got])
    ws = np.concatenate([wb for _, wb, _ in got])
    np.testing.assert_allclose(
        _fedavg(stacked, ws), _fedavg(u, w), rtol=1e-4, atol=1e-5
    )


# -- queue + staleness semantics ----------------------------------------------


def test_async_consumes_folded_and_ages_stragglers():
    n, p = 6, 48
    u, w = _mk(n, p)
    clk = ScriptedClock()
    store = UpdateStore()
    for i in range(4):
        store.write(f"c{i}", u[i], weight=float(w[i]))
    svc = _service(store, clk, monitor_timeout=0.5,
                   staleness_discount=0.5, threshold_frac=1.0)
    fused, rep = svc.aggregate(from_store=True, expected_clients=4,
                               async_round=True)
    assert store.count() == 0        # folded rows consumed
    # a straggler arrives between rounds -> folds next round at gamma^1
    store.write("late", u[4], weight=float(w[4]))
    fused2, rep2 = svc.aggregate(from_store=True, expected_clients=1,
                                 async_round=True)
    g = 0.5
    ws1 = np.einsum("np,n->p", u[:4], w[:4])
    tot1 = w[:4].sum()
    # carry decays by gamma; the late update is fresh this round (age 0)
    ws2 = g * ws1 + w[4] * u[4]
    tot2 = g * tot1 + w[4]
    np.testing.assert_allclose(
        np.asarray(fused2), ws2 / (tot2 + 1e-6), rtol=1e-4, atol=1e-5
    )


def test_staleness_discount_validation():
    with pytest.raises(ValueError):
        AggregationService(fusion="fedavg", staleness_discount=0.0)
    with pytest.raises(ValueError):
        AggregationService(fusion="fedavg", staleness_discount=1.5)


def test_async_falls_back_to_sync_for_non_streamable():
    """Fusions with no reducer decomposition (Krum) cannot fold
    incrementally: async_round is ignored and the dense path runs."""
    n, p = 6, 32
    u, _ = _mk(n, p)
    store = UpdateStore()
    for i in range(n):
        store.write(f"c{i}", u[i])
    svc = AggregationService(fusion="krum", local_strategy="jnp",
                             store=store, monitor_timeout=0.5)
    fused, rep = svc.aggregate(from_store=True, expected_clients=n,
                               async_round=True)
    assert not rep.async_round and not rep.streamed
    ref = np.asarray(get_fusion("krum").fuse(u, np.ones(n, np.float32)))
    np.testing.assert_allclose(np.asarray(fused), ref, rtol=1e-5, atol=1e-6)


def test_async_falls_back_to_sync_over_carve_budget():
    """An order-statistic round whose carve state exceeds the budget
    runs synchronously (dense) even with async_round=True."""
    n, p = 6, 32
    u, _ = _mk(n, p)
    store = UpdateStore()
    for i in range(n):
        store.write(f"c{i}", u[i])
    svc = AggregationService(fusion="coordmedian", local_strategy="jnp",
                             store=store, monitor_timeout=0.5,
                             robust_state_budget=64)
    fused, rep = svc.aggregate(from_store=True, expected_clients=n,
                               async_round=True)
    assert not rep.async_round and not rep.streamed
    assert rep.notes and "budget" in rep.notes[0]
    np.testing.assert_allclose(
        np.asarray(fused), np.median(u, axis=0), rtol=1e-5, atol=1e-6
    )


def test_async_without_expected_clients_is_timeout_gated():
    """Async rounds start BEFORE arrivals by design; with no
    expected_clients the gate must run the full timeout window and fold
    everything that lands — not close on the first client (the
    threshold=1 default the serialized path tolerated)."""
    n, p = 5, 32
    u, w = _mk(n, p)
    clk = ScriptedClock()
    store = UpdateStore()   # empty at round start
    for i in range(n):
        clk.at(0.3 * (i + 1),
               lambda i=i: store.write(f"c{i}", u[i], weight=float(w[i])))
    svc = _service(store, clk, monitor_timeout=2.0)
    fused, rep = svc.aggregate(from_store=True, async_round=True)
    assert rep.n_clients == n, "gate closed before the stragglers landed"
    assert not rep.monitor.ready    # timeout-gated rounds never 'fill'
    np.testing.assert_allclose(np.asarray(fused), _fedavg(u, w),
                               rtol=1e-4, atol=1e-5)


def test_async_rewrite_during_round_not_lost():
    """A client that re-writes its update AFTER the round folded the old
    version must not lose the new one to the post-round consume: the
    version-checked remove keeps it for the next round."""
    n, p = 4, 32
    u, w = _mk(n + 1, p)
    clk = ScriptedClock()
    store = UpdateStore()
    for i in range(n):
        store.write(f"c{i}", u[i], weight=float(w[i]))
    # c0 re-writes while the round is in flight, after its fold but
    # before the gate closes (threshold n is met only at t=0.5)
    clk.at(0.3, lambda: store.write("c0", u[n], weight=9.0))
    clk.at(0.5, lambda: store.write("late-filler", u[n], weight=1.0))

    svc = _service(store, clk, monitor_timeout=2.0,
                   stream_chunk_bytes=2 * p * 4)  # chunk of 2: early fold
    fused, rep = svc.aggregate(from_store=True, expected_clients=n + 1,
                               async_round=True)
    # the re-written c0 survived the consume for the NEXT round
    assert store.client_ids() == ["c0"]
    nv, nw = store.read("c0")
    assert nw == 9.0
    np.testing.assert_array_equal(np.asarray(nv), u[n])


def test_fuse_stream_rejects_raw_iter_arrivals():
    """Feeding iter_arrivals (ids in the third slot) straight into an
    engine must fail loudly, not corrupt weights."""
    store = UpdateStore()
    for i in range(4):
        store.write(f"c{i}", np.ones(8, np.float32))
    eng = LocalEngine(strategy="jnp")
    with pytest.raises(TypeError, match="iter_arrivals"):
        eng.fuse_stream(
            get_fusion("fedavg"),
            store.iter_arrivals(2, lambda c, t: c >= 4),
        )


def test_async_variable_close_counts_share_one_executable():
    """Rounds closing at different arrival counts (single ragged block)
    must reuse the executable keyed on the CONFIGURED chunk, not the
    observed block size — and that is the key _warm_engines probes."""
    from repro.utils import jitcache

    p = 40
    u, w = _mk(8, p)
    f = get_fusion("fedavg")
    eng = LocalEngine(strategy="jnp")
    chunk = 8
    out1, rep1 = eng.fuse_stream(f, [(u[:5], w[:5])], chunk_rows=chunk)
    assert rep1.chunk_rows == chunk
    assert eng.is_warm_stream(f, chunk, p, np.float32)
    before = jitcache.trace_count()
    out2, rep2 = eng.fuse_stream(f, [(u[:7], w[:7])], chunk_rows=chunk)
    assert jitcache.trace_count() == before, "variable close count re-traced"
    assert rep2.compile_seconds == 0.0
    np.testing.assert_allclose(np.asarray(out2), _fedavg(u[:7], w[:7]),
                               rtol=1e-4, atol=1e-5)


def test_async_phase_ingest_excludes_idle_wait():
    """phase_seconds['ingest'] on an async round is block-staging I/O,
    not the straggler wait (which is the overlap phase)."""
    n, p = 6, 64
    u, w = _mk(n, p)
    clk = ScriptedClock()
    store = UpdateStore()
    for i in range(n):
        clk.at(0.5 * (i + 1),
               lambda i=i: store.write(f"c{i}", u[i], weight=float(w[i])))
    svc = _service(store, clk, monitor_timeout=10.0)
    fused, rep = svc.aggregate(from_store=True, expected_clients=n,
                               async_round=True)
    # 3 s of scripted wait; real I/O for 6 tiny rows is far under 1 s
    assert rep.overlap_seconds >= 3.0
    assert rep.phase_seconds["overlap"] >= 3.0
    assert rep.phase_seconds["ingest"] < 1.0


# -- planner overlap costing ---------------------------------------------------


def test_planner_prefers_async_when_wait_dominates():
    planner = Planner(n_devices=1)
    f = get_fusion("fedavg")
    load = Workload(update_bytes=4 << 20, n_clients=64)
    assert planner.prefer_async(load, f, expected_wait=5.0)
    assert not planner.prefer_async(load, f, expected_wait=0.0)
    assert not planner.prefer_async(load, get_fusion("krum"), 5.0)
    plan = planner.plan(load, f)
    ser, ovl = planner.overlap_estimate(plan, expected_wait=5.0)
    assert ser == pytest.approx(5.0 + plan.est_seconds)
    assert ovl == pytest.approx(
        max(5.0, plan.est_seconds) + planner.overlap_drain_seconds
    )


# -- store fixes ---------------------------------------------------------------


def test_store_read_returns_immutable_view():
    store = UpdateStore()
    store.write("a", np.arange(8, dtype=np.float32))
    u, _ = store.read("a")
    assert not u.flags.writeable
    with pytest.raises(ValueError):
        u[0] = 99.0
    # the spool itself is untouched by the attempt
    fresh, _ = store.read("a")
    assert fresh[0] == 0.0


def test_store_clear_resets_stats_and_unlinks(tmp_path):
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.write("a", np.ones(16, np.float32), weight=2.0)
    store.write("b", np.ones(16, np.float32))
    store.read_stacked()
    assert store.stats.writes == 2 and store.stats.reads == 2
    assert store.stats.peak_block_bytes > 0
    store.clear()
    assert store.count() == 0
    assert store.stats.writes == 0 and store.stats.bytes_written == 0
    assert store.stats.reads == 0 and store.stats.peak_block_bytes == 0
    leftovers = [f for f in os.listdir(tmp_path)]
    assert leftovers == []
    # a fresh incarnation recovers nothing
    assert UpdateStore(backend="disk", spool_dir=str(tmp_path)).count() == 0


def test_store_remove_consumes_subset(tmp_path):
    for backend, kw in (("memory", {}),
                        ("disk", {"spool_dir": str(tmp_path)})):
        store = UpdateStore(backend=backend, **kw)
        for i in range(5):
            store.write(f"c{i}", np.full(4, i, np.float32))
        store.remove(["c1", "c3", "missing-id"])
        assert store.client_ids() == ["c0", "c2", "c4"]
        u, _ = store.read("c2")
        assert u[0] == 2.0


# -- distributed compile phase -------------------------------------------------


def test_distributed_cold_vs_warm_compile_phase():
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = DistributedEngine(mesh=mesh)
    f = get_fusion("iteravg")
    n, p = 10, 129
    u, w = _mk(n, p)
    ref = np.asarray(LocalEngine(strategy="jnp").fuse(f, u, w))
    out1 = np.asarray(eng.fuse(f, u, w))
    cold = eng.last_compile_seconds
    out2 = np.asarray(eng.fuse(f, u, w))
    warm = eng.last_compile_seconds
    assert cold > 0.0 and warm == 0.0
    np.testing.assert_allclose(out1, ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out2, ref, rtol=1e-4, atol=1e-5)


def test_distributed_is_warm_stream():
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = DistributedEngine(mesh=mesh)
    f = get_fusion("fedavg")
    u, w = _mk(8, 64)
    assert not eng.is_warm_stream(f, 4, 64, np.float32)
    eng.fuse_stream(f, [(u[:4], w[:4]), (u[4:], w[4:])])
    assert eng.is_warm_stream(f, 4, 64, np.float32)
    assert not eng.is_warm_stream(f, 5, 64, np.float32)
