"""Concurrent round EXECUTION on one service (ISSUE 5):

  * stress — >=4 tenants' rounds run genuinely concurrently on ONE
    AggregationService for >=20 rounds each (threaded writers + the
    RoundScheduler), every round's fused vector matching the
    isolated-store dense formula and the CompiledCache recording
    exactly one cold compile per shape bucket;
  * CompiledCache single-flight — racing threads on one key compile
    once and share the executable (and a failed build hands the slot
    to a waiter instead of caching the failure);
  * per-tenant quotas — reject raises before any blob lands, evict
    drops the tenant's oldest update (bumping its version) and counts
    into the tenant's StoreStats;
  * the evict-vs-closing-round race — an evicted entry's bumped
    write-version makes the closing round's version-checked remove
    skip its unlink (a re-submitted blob survives) and makes a
    mid-read eviction skip the row instead of folding stale bytes;
  * drift re-warmup — saturated drift for k consecutive rounds forces
    one static "rewarm" round and resets the tenant's EW curve;
  * the --quick benchmark smoke (tier-1 wiring for the scheduler).
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import (
    AdaptiveController,
    AggregationService,
    QuotaExceededError,
    RoundScheduler,
    UpdateStore,
)
from repro.utils.jitcache import CompiledCache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(7)


def fedavg_formula(u, w):
    return np.einsum("np,n->p", u, w) / w.sum()


# -- the tentpole stress bar --------------------------------------------------


@pytest.mark.usefixtures("lock_witness")
def test_stress_concurrent_tenants_on_one_service():
    """4 tenants x 20 rounds, all four executing at once on ONE service
    with writers racing the open rounds; per-round fused vectors must
    equal the dense formula on that tenant's round data alone, and the
    shared engine must have cold-compiled exactly once (one shape
    bucket across all tenants and rounds)."""
    k, rounds, n, p = 4, 20, 6, 256
    tenants = [f"app{i}" for i in range(k)]
    u = RNG.normal(size=(k, rounds, n, p)).astype(np.float32)
    w = RNG.uniform(1, 5, size=(k, rounds, n)).astype(np.float32)
    store = UpdateStore()
    svc = AggregationService(
        fusion="fedavg", local_strategy="jnp", store=store,
        threshold_frac=1.0, monitor_timeout=60.0,
    )
    errors = []

    def drive(kk, tenant, sched):
        try:
            for r in range(rounds):
                def write(kk=kk, r=r, tenant=tenant):
                    for i in range(n):
                        store.write(f"c{i}", u[kk, r, i],
                                    weight=float(w[kk, r, i]),
                                    tenant=tenant)
                wt = threading.Thread(target=write, daemon=True)
                wt.start()
                fused, rep = sched.submit(
                    tenant, from_store=True, expected_clients=n,
                    async_round=True,
                ).result(timeout=120)
                wt.join()
                assert rep.n_clients == n, (tenant, r, rep.n_clients)
                ref = fedavg_formula(u[kk, r], w[kk, r])
                np.testing.assert_allclose(
                    np.asarray(fused), ref, rtol=1e-4, atol=1e-5,
                    err_msg=f"{tenant} round {r}",
                )
                # queue semantics: the round consumed its whole fold
                assert store.count(tenant) == 0
        except BaseException as exc:  # surface in the main thread
            errors.append((tenant, exc))

    with RoundScheduler(svc) as sched:
        drivers = [
            threading.Thread(target=drive, args=(kk, t, sched),
                             daemon=True)
            for kk, t in enumerate(tenants)
        ]
        for d in drivers:
            d.start()
        for d in drivers:
            d.join()
    assert not errors, errors
    # one shape bucket -> exactly one cold compile for 4 tenants x 20
    # rounds (the single-flight cache bar: not <= K x buckets)
    assert svc.local.cache.misses == 1
    # per-tenant accounting saw every write
    for t in tenants:
        assert store.stats_for(t).writes == rounds * n
    assert store.stats.writes == k * rounds * n


@pytest.mark.usefixtures("lock_witness")
def test_scheduler_same_tenant_rounds_serialize_fifo():
    store = UpdateStore()
    svc = AggregationService(
        fusion="fedavg", store=store, threshold_frac=1.0,
        monitor_timeout=5.0,
    )
    n, p = 4, 64
    u1, w1 = RNG.normal(size=(n, p)).astype(np.float32), np.ones(n, np.float32)
    u2 = RNG.normal(size=(n, p)).astype(np.float32)
    with RoundScheduler(svc) as sched:
        for i in range(n):
            store.write(f"c{i}", u1[i], tenant="a")
        f1 = sched.submit("a", from_store=True, expected_clients=n,
                          async_round=True)
        fused1, rep1 = f1.result(timeout=60)
        for i in range(n):
            store.write(f"c{i}", u2[i], tenant="a")
        f2 = sched.submit("a", from_store=True, expected_clients=n,
                          async_round=True)
        fused2, rep2 = f2.result(timeout=60)
        assert sched.tenants() == ["a"]
    np.testing.assert_allclose(
        np.asarray(fused1), fedavg_formula(u1, w1), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(fused2), fedavg_formula(u2, w1), rtol=1e-4, atol=1e-5
    )
    assert sched.submit is not None
    with pytest.raises(RuntimeError):
        sched.submit("a", from_store=True)   # shut down


@pytest.mark.usefixtures("lock_witness")
def test_concurrent_adaptive_rounds_share_controller_safely():
    """Two tenants' adaptive rounds at once: the controller's internal
    lock keeps policy derivation/observation consistent (no exception,
    both tenants end up with their own learned curves)."""
    store = UpdateStore()
    svc = AggregationService(
        fusion="fedavg", store=store, threshold_frac=1.0,
        monitor_timeout=5.0, adaptive=True,
    )
    n, p = 4, 64
    with RoundScheduler(svc) as sched:
        for r in range(3):
            for t in ("a", "b"):
                for i in range(n):
                    store.write(f"c{i}", RNG.normal(size=(p,))
                                .astype(np.float32), tenant=t)
            res = sched.run_round(["a", "b"], from_store=True,
                                  expected_clients=n, async_round=True)
            for t in ("a", "b"):
                assert res[t][1].n_clients == n
    assert set(svc.controller.tenants()) == {"a", "b"}
    assert svc.controller.model("a").rounds == 3


def test_device_concurrency_validates():
    with pytest.raises(ValueError):
        AggregationService(fusion="fedavg", device_concurrency=0)


# -- CompiledCache single-flight ---------------------------------------------


def test_compiled_cache_single_flight_under_race():
    import jax

    cache = CompiledCache("race")
    built = []
    all_started = threading.Event()

    def builder():
        built.append(1)
        # hold the build slot until every racer thread is running, so
        # they genuinely pile up on the in-flight build (event-gated,
        # not a timing-guessed sleep)
        all_started.wait(timeout=10.0)
        return lambda x: x + 1

    results = []

    def hit():
        fn, dt = cache.get(
            ("k",), builder, jax.ShapeDtypeStruct((4,), np.float32)
        )
        results.append((fn, dt))

    threads = [threading.Thread(target=hit) for _ in range(8)]
    for t in threads:
        t.start()
    all_started.set()
    for t in threads:
        t.join()
    assert len(built) == 1          # one build, shared by all racers
    assert cache.misses == 1 and cache.hits == 7
    paid = [dt for _, dt in results if dt > 0.0]
    assert len(paid) == 1           # only the builder paid compile time
    fns = {id(fn) for fn, _ in results}
    assert len(fns) == 1            # everyone shares the executable


def test_compiled_cache_failed_build_releases_slot():
    import jax

    cache = CompiledCache("fail")
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) == 1:
            raise RuntimeError("first build dies")
        return lambda x: x * 2

    spec = jax.ShapeDtypeStruct((2,), np.float32)
    with pytest.raises(RuntimeError):
        cache.get(("k",), flaky, spec)
    fn, dt = cache.get(("k",), flaky, spec)   # slot was released
    assert len(attempts) == 2 and dt > 0.0
    np.testing.assert_allclose(
        np.asarray(fn(np.ones(2, np.float32))), 2.0
    )


# -- per-tenant quotas and stats ---------------------------------------------


def test_quota_reject_raises_and_leaves_partition_intact():
    s = UpdateStore()
    s.set_quota("a", max_bytes=40, policy="reject")
    s.write("c0", np.ones(8, np.float32), tenant="a")   # 32 B: fits
    with pytest.raises(QuotaExceededError):
        s.write("c1", np.ones(8, np.float32), tenant="a")
    assert s.client_ids("a") == ["c0"]
    assert s.tenant_bytes("a") == 32
    # replacing the resident update stays within budget (delta-counted)
    s.write("c0", np.ones(8, np.float32) * 2, tenant="a")
    assert s.client_ids("a") == ["c0"]


def test_quota_reject_on_disk_leaves_no_orphan_blob(tmp_path):
    s = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    s.set_quota("default", max_updates=1, policy="reject")
    s.write("c0", np.ones(4, np.float32))
    with pytest.raises(QuotaExceededError):
        s.write("c1", np.ones(4, np.float32))
    assert not os.path.exists(tmp_path / "c1.npy")


def test_quota_evict_drops_oldest_and_counts():
    s = UpdateStore()
    s.set_quota("a", max_updates=2, policy="evict")
    s.write("c0", np.ones(4, np.float32), tenant="a")
    s.write("c1", np.ones(4, np.float32), tenant="a")
    s.write("c2", np.ones(4, np.float32), tenant="a")
    assert s.client_ids("a") == ["c1", "c2"]   # oldest arrival evicted
    assert s.stats_for("a").evictions == 1
    assert s.stats.evictions == 1
    # an update alone bigger than the byte budget rejects even under
    # evict (nothing to evict for it)
    s.set_quota("b", max_bytes=8, policy="evict")
    with pytest.raises(QuotaExceededError):
        s.write("c0", np.ones(8, np.float32), tenant="b")


def test_quota_does_not_bleed_across_tenants():
    s = UpdateStore()
    s.set_quota("noisy", max_updates=1, policy="evict")
    for i in range(5):
        s.write(f"c{i}", np.ones(4, np.float32), tenant="noisy")
        s.write(f"c{i}", np.ones(4, np.float32), tenant="quiet")
    assert s.count("noisy") == 1
    assert s.count("quiet") == 5
    assert s.stats_for("quiet").evictions == 0


def test_round_report_carries_tenant_store_stats():
    store = UpdateStore()
    svc = AggregationService(
        fusion="fedavg", store=store, threshold_frac=1.0,
        monitor_timeout=2.0,
    )
    n, p = 4, 64
    for i in range(n):
        store.write(f"c{i}", RNG.normal(size=(p,)).astype(np.float32),
                    tenant="a")
        store.write(f"x{i}", RNG.normal(size=(p,)).astype(np.float32),
                    tenant="b")
    _, rep = svc.aggregate(from_store=True, expected_clients=n,
                           tenant="a")
    assert rep.store_stats is not None
    assert rep.store_stats.writes == n          # tenant a's alone
    assert rep.store_stats.reads == n
    assert store.stats.writes == 2 * n          # legacy aggregate view


# -- evict vs closing round --------------------------------------------------


def test_eviction_version_bump_defeats_stale_unlink(tmp_path):
    """The PR-4 race, deterministically: a round folded c0 at version 1;
    c0 is then evicted and re-submitted (version moves on). The closing
    round's version-checked remove must NOT unlink the successor."""
    s = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    s.write("c0", np.ones(4, np.float32))
    folded_versions = {"c0": s._versions[("default", "c0")]}
    # eviction (what quota pressure or a re-submission does)
    with s._lock:
        s._evict_locked(("default", "c0"))
    s.write("c0", np.ones(4, np.float32) * 3)   # the re-submission
    s.remove(["c0"], versions=folded_versions)  # the round's close
    assert os.path.exists(tmp_path / "c0.npy")  # successor survived
    u, w = s.read("c0")
    np.testing.assert_allclose(u, 3.0)


def test_victim_rewritten_after_eviction_keeps_fresh_blob(tmp_path):
    """A quota-eviction victim re-written between the eviction and the
    evictor's unlink must keep its FRESH blob: the unlink re-checks the
    version recorded at eviction (the remove() guard, reused)."""
    s = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    s.set_quota("default", max_updates=1, policy="evict")
    s.write("c0", np.ones(4, np.float32))
    with s._lock:   # the eviction half of an in-flight write("c1")
        verdict, victims = s._quota_check_locked(("default", "c1"), 16)
    assert verdict == "ok" and list(victims) == [("default", "c0")]
    s.write("c0", np.ones(4, np.float32) * 7)   # re-write races the unlink
    s._unlink_evicted(victims)                  # ...which must now no-op
    assert os.path.exists(tmp_path / "c0.npy")
    u, _ = s.read("c0")
    np.testing.assert_allclose(u, 7.0)


def test_mid_read_eviction_skips_row_instead_of_folding(tmp_path,
                                                        monkeypatch):
    """A streaming read that races an eviction must DISCARD the stale
    bytes (half-unlinked blob), not fold them: the eviction bumps the
    version before touching files, and _read_versioned re-checks after
    the blob read."""
    s = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    s.write("c0", np.ones(4, np.float32))
    s.write("c1", np.ones(4, np.float32) * 2)
    orig = UpdateStore._sidecar_dtype
    evicted = []

    def evict_mid_read(path):
        # fires between the blob read and the version re-check
        if path.endswith("c0.npy") and not evicted:
            with s._lock:
                s._evict_locked(("default", "c0"))
            evicted.append(True)
        return orig(path)

    monkeypatch.setattr(UpdateStore, "_sidecar_dtype",
                        staticmethod(evict_mid_read))
    with s._lock:
        keys = s._keys("default")
    blk = s._load_block(keys)
    assert evicted
    (block, w, loaded), = blk                   # one dense sub-block
    assert block.shape[0] == 1                  # c0's row was skipped
    np.testing.assert_allclose(block.arrays[0], 2.0)   # only c1 folded
    assert loaded == [("default", "c1")]


# -- drift-triggered re-warmup ------------------------------------------------


def test_drift_saturation_forces_rewarm_and_resets_curve():
    c = AdaptiveController(
        threshold_frac=1.0, timeout=10.0,
        rewarm_drift=0.5, rewarm_patience=2,
    )
    for _ in range(3):   # steady regime
        c.observe_round("t", [0.1 * i for i in range(1, 11)], 10)
    assert c.policy("t", 10).source == "learned"
    # regime change the EW window cannot catch: drift saturates
    for r in range(3):
        c.observe_round(
            "t", [5.0 + 30 * r + 0.3 * i for i in range(1, 11)], 10
        )
    assert c.model("t").drift >= 0.5
    pol = c.policy("t", 10)
    assert pol.source == "rewarm"
    assert pol.deadline == 10.0                 # the static gate
    assert c.model("t").rounds == 0             # EW curve reset
    # next policy is NOT a prior borrow (the prior carries the stale
    # regime): static until the fresh curve warms up
    assert c.policy("t", 10).source == "static"
    c.observe_round("t", [0.1 * i for i in range(1, 11)], 10)
    assert c.policy("t", 10).source == "learned"   # re-learned


def test_rewarm_state_survives_checkpoint_roundtrip():
    c = AdaptiveController(rewarm_drift=0.5, rewarm_patience=2)
    for r in range(5):
        c.observe_round(
            "t", [1.0 + 30 * r + 0.2 * i for i in range(1, 9)], 8
        )
    state = c.state_dict()
    c2 = AdaptiveController(rewarm_drift=0.5, rewarm_patience=2)
    c2.load_state_dict(state)
    assert c2.policy("t", 8).source == c.policy("t", 8).source


def test_steady_drift_never_triggers_rewarm():
    c = AdaptiveController(rewarm_drift=0.5, rewarm_patience=2)
    for _ in range(10):
        c.observe_round("t", [0.1 * i for i in range(1, 9)], 8)
    assert c.policy("t", 8).source == "learned"


# -- tier-1 wiring for the scheduler benchmark --------------------------------


def test_concurrent_benchmark_quick_smoke(tmp_path):
    """The --quick benchmark is the scheduler's end-to-end regression
    gate: concurrent-on-one-service must beat K serialized rounds with
    full inclusion, formula-equivalent vectors, and cold compiles
    bounded by shape buckets — in tier-1, not only in full runs."""
    import json

    out = tmp_path / "BENCH_concurrent.json"
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "concurrent_service.py"),
         "--quick", "--out", str(out)],
        capture_output=True, text=True, timeout=280,
        env={**os.environ,
             "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(out.read_text())
    assert payload["acceptance"] is True, payload
    assert payload["results"]["concurrent"]["cold_compiles"] <= \
        payload["shape_buckets"]
