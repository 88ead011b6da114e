"""Tenant-partitioned UpdateStore and the multi-tenant service path:

  * store partitioning — per-tenant count/client_ids/meta/read filters,
    the same client id under two tenants staying independent, per-tenant
    iter_chunks/iter_arrivals/read_stacked/remove/clear;
  * no-steal — interleaved open rounds on ONE store never fold another
    tenant's updates (scripted-clock exactness + genuinely concurrent
    threads), and shared-store rounds produce the same report/result as
    isolated per-tenant stores (the ISSUE-4 equivalence bar);
  * disk spool layout — default tenant at the root (restart-compatible),
    other tenants in subdirectories; restart recovery; external-blob
    tenant routing by subdirectory and by ``.tenant`` sidecar;
    SpoolTailer discovery of tenant subdirectories;
  * adaptive follow-ons — cross-tenant prior for cold-start tenants,
    drift detection widening the learned deadline, and controller
    checkpoint/restore via ``repro.checkpoint`` (a restarted service
    resumes learned, not cold).
"""
import bisect
import os
import threading
import time

import numpy as np
import pytest

from repro.checkpoint import load_controller_state, save_controller_state
from repro.core import (
    AdaptiveController,
    AggregationService,
    ArrivalModel,
    SpoolTailer,
    UpdateStore,
)
from repro.core.compress import stack_block

RNG = np.random.default_rng(123)


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


def _mk(n, p=32):
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
    return u, w


def _fedavg(u, w):
    return np.einsum("np,n->p", u, w) / (w.sum() + 1e-6)


def _service(store, clk=None, **kw):
    kw.setdefault("threshold_frac", 1.0)
    kw.setdefault("monitor_timeout", 30.0)
    extra = {}
    if clk is not None:
        extra = {"clock": clk.clock, "sleep": clk.sleep}
    return AggregationService(
        fusion="fedavg", local_strategy="jnp", store=store, **extra, **kw
    )


# -- store partitioning --------------------------------------------------------


def test_store_partitions_by_tenant():
    store = UpdateStore()
    store.write("c0", np.ones(4, np.float32), weight=2.0, tenant="A")
    store.write("c1", np.full(4, 2.0, np.float32), tenant="A")
    store.write("c0", np.full(4, 7.0, np.float32), weight=3.0, tenant="B")
    store.write("u0", np.zeros(4, np.float32))   # untagged -> default
    assert store.count() == 4                    # whole-spool view
    assert store.count("A") == 2
    assert store.count("B") == 1
    assert store.count("default") == 1
    assert store.count("nope") == 0
    assert store.client_ids("A") == ["c0", "c1"]
    assert store.client_ids("B") == ["c0"]
    assert store.tenants() == ["A", "B", "default"]
    # the same client id under two tenants: independent updates
    ua, wa = store.read("c0", tenant="A")
    ub, wb = store.read("c0", tenant="B")
    assert wa == 2.0 and wb == 3.0
    np.testing.assert_array_equal(np.asarray(ua), np.ones(4, np.float32))
    np.testing.assert_array_equal(np.asarray(ub),
                                  np.full(4, 7.0, np.float32))
    n, p, _ = store.meta("A")
    assert (n, p) == (2, 4)
    with pytest.raises(LookupError):
        store.meta("nope")


def test_store_per_tenant_streams_and_consume():
    u, w = _mk(6, 8)
    store = UpdateStore()
    for i in range(3):
        store.write(f"c{i}", u[i], weight=float(w[i]), tenant="A")
    for i in range(3, 6):
        store.write(f"c{i}", u[i], weight=float(w[i]), tenant="B")
    stacked, ws = store.read_stacked(tenant="A")
    np.testing.assert_array_equal(stacked, u[:3])
    np.testing.assert_array_equal(ws, w[:3])
    blocks = list(store.iter_chunks(2, tenant="B"))
    got = np.concatenate([stack_block(b) for b, _ in blocks])
    np.testing.assert_array_equal(got, u[3:])
    # arrival timestamps filter too
    assert set(store.arrival_times("A")) == {"c0", "c1", "c2"}
    # consume is tenant-scoped: removing A's ids never touches B's
    store.remove(["c0", "c1", "c2"], tenant="A")
    assert store.count("A") == 0
    assert store.count("B") == 3
    store.clear(tenant="B")
    assert store.count() == 0


def test_iter_arrivals_filters_tenant():
    """An open arrival stream for tenant A never yields B's concurrent
    writes — the property that makes interleaved open rounds safe."""
    u, w = _mk(6, 8)
    clk = ScriptedClock()
    store = UpdateStore(clock=clk.clock)
    for i in range(2):
        store.write(f"a{i}", u[i], weight=float(w[i]), tenant="A")
    # B's updates land WHILE A's stream is open
    clk.at(0.1, lambda: store.write("b0", u[3], tenant="B"))
    clk.at(0.2, lambda: store.write("a2", u[2], weight=float(w[2]),
                                    tenant="A"))
    got = list(store.iter_arrivals(
        2, lambda count, waited: count >= 3 or waited > 5.0,
        clock=clk.clock, sleep=clk.sleep, tenant="A",
    ))
    ids = [cid for _, _, batch in got for cid in batch]
    assert ids == ["a0", "a1", "a2"]     # b0 never entered the stream
    assert store.count("B") == 1


# -- no-steal / shared-vs-isolated equivalence ---------------------------------


def test_interleaved_rounds_do_not_steal(tmp_path):
    """Scripted-clock exactness: A's and B's writes interleave in one
    store; A's async round folds exactly A's fleet, leaves B's
    partition intact, and B's round then folds exactly B's."""
    na, nb, p = 4, 3, 16
    ua, wa = _mk(na, p)
    ub, wb = _mk(nb, p)
    clk = ScriptedClock()
    store = UpdateStore(clock=clk.clock)
    svc = _service(store, clk)

    for i in range(na):
        clk.at(0.1 * (i + 1),
               lambda i=i: store.write(f"c{i}", ua[i],
                                       weight=float(wa[i]), tenant="A"))
    for i in range(nb):   # same client ids, interleaved timing
        clk.at(0.05 + 0.1 * (i + 1),
               lambda i=i: store.write(f"c{i}", ub[i],
                                       weight=float(wb[i]), tenant="B"))

    fused_a, rep_a = svc.aggregate(from_store=True, expected_clients=na,
                                   async_round=True, tenant="A")
    assert rep_a.n_clients == na and rep_a.tenant == "A"
    np.testing.assert_allclose(np.asarray(fused_a), _fedavg(ua, wa),
                               rtol=1e-4, atol=1e-5)
    # A's consume left B's partition whole
    assert store.count("A") == 0
    assert store.count("B") == nb
    fused_b, rep_b = svc.aggregate(from_store=True, expected_clients=nb,
                                   async_round=True, tenant="B")
    assert rep_b.n_clients == nb
    np.testing.assert_allclose(np.asarray(fused_b), _fedavg(ub, wb),
                               rtol=1e-4, atol=1e-5)


def test_shared_store_matches_isolated_stores():
    """The ISSUE-4 equivalence bar: two tenants with interleaved open
    rounds on ONE store produce the same RoundReport substance
    (included count, ready, result) as the same tenants on isolated
    stores — here with genuinely concurrent rounds (one service per
    tenant, one shared store, real threads)."""
    n, p = 6, 24
    u = {t: _mk(n, p) for t in ("A", "B")}

    def run_shared():
        store = UpdateStore()
        out = {}

        def one_round(tenant):
            svc = _service(store, poll_interval=0.005)
            for i in range(n):
                time.sleep(0.02)
                store.write(f"c{i}", u[tenant][0][i],
                            weight=float(u[tenant][1][i]), tenant=tenant)
            out[tenant] = svc.aggregate(
                from_store=True, expected_clients=n, async_round=True,
                tenant=tenant,
            )

        threads = [
            threading.Thread(target=one_round, args=(t,))
            for t in ("A", "B")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out

    def run_isolated():
        out = {}
        for tenant in ("A", "B"):
            store = UpdateStore()
            svc = _service(store, poll_interval=0.005)
            for i in range(n):
                store.write(f"c{i}", u[tenant][0][i],
                            weight=float(u[tenant][1][i]), tenant=tenant)
            out[tenant] = svc.aggregate(
                from_store=True, expected_clients=n, async_round=True,
                tenant=tenant,
            )
        return out

    shared, isolated = run_shared(), run_isolated()
    for tenant in ("A", "B"):
        fs, rs = shared[tenant]
        fi, ri = isolated[tenant]
        assert rs.n_clients == ri.n_clients == n
        assert rs.monitor.ready and ri.monitor.ready
        assert rs.tenant == ri.tenant == tenant
        np.testing.assert_allclose(np.asarray(fs), np.asarray(fi),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(fs), _fedavg(*u[tenant]), rtol=1e-4, atol=1e-5,
        )


# -- disk spool layout / routing -----------------------------------------------


def test_disk_spool_tenant_layout_and_recovery(tmp_path):
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.write("c0", np.ones(4, np.float32), weight=2.0)
    store.write("c0", np.full(4, 3.0, np.float32), weight=1.5,
                tenant="appX")
    # default at the root, tenant in its subdirectory
    assert os.path.exists(tmp_path / "c0.npy")
    assert os.path.exists(tmp_path / "appX" / "c0.npy")
    # a new incarnation recovers both partitions
    store2 = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    assert store2.count("default") == 1
    assert store2.count("appX") == 1
    upd, weight = store2.read("c0", tenant="appX")
    assert weight == 1.5
    np.testing.assert_array_equal(np.asarray(upd),
                                  np.full(4, 3.0, np.float32))
    # per-tenant clear unlinks only that partition's blobs
    store2.clear(tenant="appX")
    assert not os.path.exists(tmp_path / "appX" / "c0.npy")
    assert os.path.exists(tmp_path / "c0.npy")


def test_ingest_external_tenant_subdir(tmp_path):
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path),
                        sidecar_grace_seconds=0.05)
    os.makedirs(tmp_path / "appY")
    np.save(tmp_path / "appY" / "e0.npy", np.full(8, 5.0, np.float32))
    with open(tmp_path / "appY" / "e0.npy.w", "w") as f:
        f.write("4.0")
    assert store.ingest_external() == ["e0"]
    assert store.count("appY") == 1
    upd, weight = store.read("e0", tenant="appY")
    assert weight == 4.0
    assert "e0" in store.arrival_times("appY")
    # idempotent
    assert store.ingest_external() == []


def test_ingest_external_tenant_sidecar_routes_and_moves(tmp_path):
    """A root-level blob with a ``.tenant`` sidecar registers under the
    named tenant and its files move into the tenant subdirectory."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    np.save(tmp_path / "e1.npy", np.full(4, 2.0, np.float32))
    with open(tmp_path / "e1.npy.tenant", "w") as f:
        f.write("appZ")
    with open(tmp_path / "e1.npy.w", "w") as f:
        f.write("2.5")
    assert store.ingest_external() == ["e1"]
    assert store.count("appZ") == 1
    assert store.count("default") == 0
    assert os.path.exists(tmp_path / "appZ" / "e1.npy")
    assert not os.path.exists(tmp_path / "e1.npy")
    assert not os.path.exists(tmp_path / "e1.npy.tenant")
    _, weight = store.read("e1", tenant="appZ")
    assert weight == 2.5


def test_tenant_sidecar_waits_for_weight_sidecar(tmp_path):
    """The review race: ``.tenant`` lands but ``.w`` is still in flight
    — the move/registration must defer so the weight is not frozen at
    the 1.0 default with an orphaned ``.w`` at the root."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    np.save(tmp_path / "e2.npy", np.ones(4, np.float32))
    with open(tmp_path / "e2.npy.tenant", "w") as f:
        f.write("appW")
    assert store.ingest_external() == []     # within grace: no move yet
    assert os.path.exists(tmp_path / "e2.npy")
    with open(tmp_path / "e2.npy.w", "w") as f:
        f.write("9.0")
    assert store.ingest_external() == ["e2"]
    _, weight = store.read("e2", tenant="appW")
    assert weight == 9.0
    assert not os.path.exists(tmp_path / "e2.npy.w")   # moved, not orphaned


def test_late_tenant_sidecar_cannot_steal_registered_bytes(tmp_path):
    """Once a blob registers, its bytes belong to that entry: a
    ``.tenant`` sidecar arriving late (out of the documented blob ->
    .tenant -> .w order) is removed, never honored — a stray sidecar
    alone must not move a live registration's payload cross-tenant."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path),
                        sidecar_grace_seconds=0.01)
    np.save(tmp_path / "e3.npy", np.full(4, 6.0, np.float32))
    with open(tmp_path / "e3.npy.w", "w") as f:
        f.write("2.0")
    assert store.ingest_external() == ["e3"]
    assert store.count("default") == 1
    with open(tmp_path / "e3.npy.tenant", "w") as f:   # late sidecar
        f.write("appV")
    assert store.ingest_external() == []
    assert store.count("default") == 1 and store.count("appV") == 0
    assert not os.path.exists(tmp_path / "e3.npy.tenant")  # cleaned up
    upd, weight = store.read("e3")          # still the default's, intact
    assert weight == 2.0
    np.testing.assert_array_equal(np.asarray(upd),
                                  np.full(4, 6.0, np.float32))


def test_stray_sidecar_on_api_written_entry_is_ignored(tmp_path):
    """A stray ``.tenant`` sidecar dropped next to a ``write()``-
    registered default blob (no new blob bytes) must not reroute the
    client's live update."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.write("w7", np.full(4, 5.0, np.float32), weight=3.0)
    with open(tmp_path / "w7.npy.tenant", "w") as f:
        f.write("appR")
    assert store.ingest_external() == []
    assert store.count("default") == 1 and store.count("appR") == 0
    assert not os.path.exists(tmp_path / "w7.npy.tenant")
    upd, weight = store.read("w7")
    assert weight == 3.0
    np.testing.assert_array_equal(np.asarray(upd),
                                  np.full(4, 5.0, np.float32))


def test_resubmission_after_restart_still_reroutes(tmp_path):
    """Root-blob ownership survives restarts: a genuine byte-replacing
    re-submission landing AFTER a new store incarnation recovered the
    entry must still evict + re-route (recovery records blob mtimes)."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.write("c8", np.ones(4, np.float32), weight=2.0)
    store2 = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    assert store2.count("default") == 1
    np.save(tmp_path / "c8.npy", np.full(4, 7.0, np.float32))  # new bytes
    with open(tmp_path / "c8.npy.tenant", "w") as f:
        f.write("appQ")
    with open(tmp_path / "c8.npy.w", "w") as f:
        f.write("5.0")
    assert store2.ingest_external() == ["c8"]
    assert store2.count("default") == 0 and store2.count("appQ") == 1
    _, weight = store2.read("c8", tenant="appQ")
    assert weight == 5.0


def test_empty_rounds_do_not_pollute_prior():
    """One dead tenant's timed-out rounds must not drag the
    cross-tenant prior's attainable fraction (and with it every
    cold-start tenant's borrowed threshold) toward zero."""
    c = AdaptiveController(cost_bias=0.5, threshold_frac=1.0,
                           timeout=30.0)
    for _ in range(3):
        c.observe_round("healthy", np.linspace(0.1, 1.0, 10), 10)
        c.observe_round("dead", [], 10)     # fleet down: empty rounds
    assert c.model("dead").attainable == pytest.approx(0.0, abs=0.2)
    assert c.prior_model().attainable == pytest.approx(1.0)
    pol = c.policy("fresh", 10)
    assert pol.source == "prior"
    assert pol.threshold == 10              # full fleet, not threshold=1


def test_recover_skips_npy_named_tenant_directories(tmp_path):
    """A tenant whose name ends in .npy creates spool_dir/<name>/ — a
    restart must not register the DIRECTORY as a phantom default blob."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.write("c0", np.ones(4, np.float32), weight=2.0, tenant="x.npy")
    store2 = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    assert store2.count("default") == 0      # no phantom 'x' blob
    assert store2.count("x.npy") == 1
    n, p, _ = store2.meta("x.npy")           # reads resolve fine
    assert (n, p) == (1, 4)


def test_resubmitted_external_blob_does_not_clobber_registration(tmp_path):
    """A root re-submission of an already-registered (tenant, cid) must
    not move/overwrite the registered blob out from under the index and
    its version guard — it waits at the root until the registered one
    is consumed."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))

    def submit(value, weight):
        np.save(tmp_path / "e4.npy", np.full(4, value, np.float32))
        with open(tmp_path / "e4.npy.tenant", "w") as f:
            f.write("appU")
        with open(tmp_path / "e4.npy.w", "w") as f:
            f.write(repr(weight))

    submit(1.0, 2.0)
    assert store.ingest_external() == ["e4"]
    submit(9.0, 5.0)                       # re-submission, still at root
    assert store.ingest_external() == []   # registered entry wins
    upd, weight = store.read("e4", tenant="appU")
    assert weight == 2.0                   # NOT clobbered by the re-submit
    np.testing.assert_array_equal(np.asarray(upd),
                                  np.ones(4, np.float32))
    # once the registered update is consumed, the re-submission lands
    store.remove(["e4"], tenant="appU")
    assert store.ingest_external() == ["e4"]
    upd, weight = store.read("e4", tenant="appU")
    assert weight == 5.0
    np.testing.assert_array_equal(np.asarray(upd),
                                  np.full(4, 9.0, np.float32))


def test_external_default_subdir_routes_to_root_partition(tmp_path):
    """A literal ``default/`` subdirectory registers into the root
    partition (files moved there) instead of being silently skipped."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    os.makedirs(tmp_path / "default")
    np.save(tmp_path / "default" / "d0.npy", np.full(4, 3.0, np.float32))
    with open(tmp_path / "default" / "d0.npy.w", "w") as f:
        f.write("1.5")
    assert store.ingest_external() == ["d0"]
    assert store.count("default") == 1
    assert os.path.exists(tmp_path / "d0.npy")
    assert not os.path.exists(tmp_path / "default" / "d0.npy")
    upd, weight = store.read("d0")
    assert weight == 1.5
    np.testing.assert_array_equal(np.asarray(upd),
                                  np.full(4, 3.0, np.float32))


def test_invalid_tenant_names_rejected(tmp_path):
    """Tenant names become spool subdirectories: path separators and
    traversal are rejected at write, and a poisoned ``.tenant`` sidecar
    never routes (no files escape the spool)."""
    store = UpdateStore()
    for bad in ("", "a/b", "..", ".", "a\\b", "../../tmp/evil"):
        with pytest.raises(ValueError):
            store.write("c0", np.ones(4, np.float32), tenant=bad)
    disk = UpdateStore(backend="disk", spool_dir=str(tmp_path / "spool"),
                       sidecar_grace_seconds=0.0)
    np.save(tmp_path / "spool" / "x.npy", np.ones(4, np.float32))
    with open(tmp_path / "spool" / "x.npy.tenant", "w") as f:
        f.write("../../escape")
    with open(tmp_path / "spool" / "x.npy.w", "w") as f:
        f.write("1.0")
    assert disk.ingest_external() == []          # quarantined, not routed
    assert disk.count() == 0
    assert os.path.exists(tmp_path / "spool" / "x.npy")  # never moved
    assert not os.path.exists(tmp_path / "escape")


def test_sidecar_route_colliding_with_default_cid_evicts_stale_entry(
    tmp_path,
):
    """The root staging namespace is shared: a sidecar-routed
    submission reusing a live default-tenant cid has already
    overwritten its blob bytes — the store must evict the stale default
    entry instead of folding another tenant's payload into the default
    round."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.write("c9", np.ones(4, np.float32), weight=2.0)   # default
    assert store.count("default") == 1
    # external writer reuses the cid via the root+sidecar route
    np.save(tmp_path / "c9.npy", np.full(4, 8.0, np.float32))
    with open(tmp_path / "c9.npy.tenant", "w") as f:
        f.write("appS")
    with open(tmp_path / "c9.npy.w", "w") as f:
        f.write("4.0")
    assert store.ingest_external() == ["c9"]
    assert store.count("default") == 0     # stale entry evicted
    assert store.count("appS") == 1
    upd, weight = store.read("c9", tenant="appS")
    assert weight == 4.0
    np.testing.assert_array_equal(np.asarray(upd),
                                  np.full(4, 8.0, np.float32))


def test_recover_leaves_pending_sidecar_routing_to_ingest(tmp_path):
    """Restart with a root blob whose ``.tenant`` sidecar names another
    tenant: _recover must NOT register it under default (cross-tenant
    steal) — it stays unregistered until ingest_external routes it."""
    np.save(tmp_path / "r0.npy", np.full(4, 2.0, np.float32))
    with open(tmp_path / "r0.npy.tenant", "w") as f:
        f.write("appT")
    with open(tmp_path / "r0.npy.w", "w") as f:
        f.write("3.0")
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    assert store.count("default") == 0      # not stolen by recovery
    assert store.count("appT") == 0
    assert store.ingest_external() == ["r0"]
    assert store.count("appT") == 1
    _, weight = store.read("r0", tenant="appT")
    assert weight == 3.0


def test_recover_leaves_default_subdir_to_ingest(tmp_path):
    """Restart with a literal ``default/`` subdirectory: _recover must
    not register it in place (its read paths resolve to the root) —
    ingest_external moves and registers it."""
    os.makedirs(tmp_path / "default")
    np.save(tmp_path / "default" / "d1.npy", np.full(4, 4.0, np.float32))
    with open(tmp_path / "default" / "d1.npy.w", "w") as f:
        f.write("2.0")
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    assert store.count() == 0
    assert store.ingest_external() == ["d1"]
    upd, weight = store.read("d1")          # readable at the ROOT path
    assert weight == 2.0
    np.testing.assert_array_equal(np.asarray(upd),
                                  np.full(4, 4.0, np.float32))


def test_spool_tailer_discovers_tenant_subdirs(tmp_path):
    """External writes into a tenant subdirectory created AFTER the
    tailer started are still discovered and routed."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    with SpoolTailer(store, poll_interval=0.05):
        def foreign_writer():
            time.sleep(0.1)
            os.makedirs(tmp_path / "late-tenant")
            np.save(tmp_path / "late-tenant" / "x.npy",
                    np.ones(4, np.float32))
            with open(tmp_path / "late-tenant" / "x.npy.w", "w") as f:
                f.write("1.5")
        th = threading.Thread(target=foreign_writer)
        th.start()
        deadline = time.time() + 5.0
        while store.count("late-tenant") < 1 and time.time() < deadline:
            time.sleep(0.02)
        th.join()
        assert store.count("late-tenant") == 1
        _, weight = store.read("x", tenant="late-tenant")
        assert weight == 1.5


# -- cross-tenant prior (cold-start transfer) ----------------------------------


def test_cold_start_tenant_borrows_prior():
    """A tenant with no history gets a policy derived from the pooled
    cross-tenant curve instead of the static timeout."""
    c = AdaptiveController(cost_bias=0.5, threshold_frac=1.0,
                           timeout=30.0)
    # tenant A: 8 of 10 arrive within 1 s, 2 drop
    for _ in range(3):
        c.observe_round("A", np.linspace(0.1, 1.0, 8), 10)
    pol = c.policy("fresh-tenant", 10)
    assert pol.source == "prior"
    assert pol.threshold == 8          # the prior's attainable fleet
    assert pol.deadline < 5.0          # ~A's tail, not the 30 s timeout
    # once the tenant has its own mass, its own curve takes over
    c.observe_round("fresh-tenant", np.linspace(0.05, 0.2, 10), 10)
    own = c.policy("fresh-tenant", 10)
    assert own.source == "learned"
    assert own.deadline < pol.deadline  # its fleet is faster than A's


def test_prior_survives_state_dict_roundtrip():
    c = AdaptiveController(cost_bias=0.5, threshold_frac=1.0,
                           timeout=30.0)
    for _ in range(2):
        c.observe_round("A", np.linspace(0.1, 0.6, 10), 10)
    c2 = AdaptiveController(cost_bias=0.5, threshold_frac=1.0,
                            timeout=30.0)
    c2.load_state_dict(c.state_dict())
    assert c2.prior_model().rounds == c.prior_model().rounds
    assert c2.policy("unseen", 10) == c.policy("unseen", 10)
    assert c2.policy("unseen", 10).source == "prior"


def test_service_cold_tenant_closes_on_prior():
    """End to end: tenant A trains the prior; tenant B's FIRST round
    already closes early instead of burning the static timeout."""
    n, p = 8, 24
    u, w = _mk(n, p)
    clk = ScriptedClock()
    store = UpdateStore(clock=clk.clock)
    svc = _service(store, clk, adaptive=True)

    def schedule(tenant, base):
        for i in range(n):
            clk.at(base + 0.1 * (i + 1),
                   lambda i=i: store.write(f"c{i}", u[i],
                                           weight=float(w[i]),
                                           tenant=tenant))

    schedule("A", 0.0)
    _, rep1 = svc.aggregate(from_store=True, expected_clients=10,
                            async_round=True, tenant="A")
    assert rep1.close_policy.source == "static"
    assert rep1.monitor.waited >= 30.0      # static gate burns the timeout

    schedule("B", clk.t)
    _, rep2 = svc.aggregate(from_store=True, expected_clients=10,
                            async_round=True, tenant="B")
    assert rep2.close_policy.source == "prior"
    assert rep2.n_clients == n              # same inclusion as A achieved
    assert rep2.monitor.waited < 3.0        # closed on the borrowed curve


# -- drift detection -----------------------------------------------------------


def test_drift_tracks_regime_change_and_decays():
    m = ArrivalModel(n_quantiles=10, ema=0.5)
    for _ in range(3):
        m.observe(np.linspace(0.1, 1.0, 10), expected=10)
    assert m.drift == pytest.approx(0.0, abs=1e-9)   # steady state
    m.observe(np.linspace(0.4, 4.0, 10), expected=10)  # 4x slowdown
    assert m.drift is not None and m.drift > 0.3
    for _ in range(6):   # new regime becomes the steady state again
        m.observe(np.linspace(0.4, 4.0, 10), expected=10)
    assert m.drift < 0.1


def test_drift_widens_learned_deadline_capped_at_timeout():
    mk = lambda: AdaptiveController(cost_bias=0.5, threshold_frac=1.0,
                                    timeout=30.0)
    steady, shifted = mk(), mk()
    for _ in range(3):
        steady.observe_round("m", np.linspace(0.1, 1.0, 10), 10)
        shifted.observe_round("m", np.linspace(0.1, 1.0, 10), 10)
    # the shifted fleet slows down 3x in ONE round — faster than the EW
    # window has tracked, so the deadline backstop must loosen
    shifted.observe_round("m", np.linspace(0.3, 3.0, 10), 10)
    pol_steady = steady.policy("m", 10)
    pol_shifted = shifted.policy("m", 10)
    assert shifted.model("m").drift > steady.model("m").drift
    # compare the deadline each policy grants per second of expected
    # wait — the widening factor, independent of the curve itself
    ratio_steady = pol_steady.deadline / pol_steady.expected_wait
    ratio_shifted = pol_shifted.deadline / pol_shifted.expected_wait
    assert ratio_shifted > ratio_steady * 1.2
    assert pol_shifted.deadline <= 30.0


# -- controller checkpoint / restart -------------------------------------------


def test_controller_checkpoint_roundtrip_files(tmp_path):
    c = AdaptiveController(cost_bias=0.5, threshold_frac=1.0,
                           timeout=30.0)
    for _ in range(3):
        c.observe_round("m", np.linspace(0.1, 1.0, 8), 10,
                        est_seconds=0.02)
    path = save_controller_state(str(tmp_path / "round7.npz"), c)
    assert path.endswith(".controller.json")
    c2 = AdaptiveController(cost_bias=0.5, threshold_frac=1.0,
                            timeout=30.0)
    load_controller_state(str(tmp_path / "round7.npz"), c2)
    assert c2.tenants() == ["m"]
    assert c2.policy("m", 10) == c.policy("m", 10)
    assert c2.policy("m", 10).source == "learned"


def test_restarted_service_resumes_learned(tmp_path):
    """The ISSUE-4 acceptance bar: a restarted service restores the
    controller from repro/checkpoint and its FIRST round closes on the
    learned gate — no cold-start re-learning."""
    n, p = 8, 24
    u, w = _mk(n, p)
    ckpt = str(tmp_path / "model")

    def schedule(clk, store, base):
        for i in range(n):
            clk.at(base + 0.1 * (i + 1),
                   lambda i=i: store.write(f"c{i}", u[i],
                                           weight=float(w[i])))

    clk1 = ScriptedClock()
    store1 = UpdateStore(clock=clk1.clock)
    svc1 = _service(store1, clk1, adaptive=True)
    schedule(clk1, store1, 0.0)
    _, rep1 = svc1.aggregate(from_store=True, expected_clients=10,
                             async_round=True)
    assert rep1.close_policy.source == "static"   # cold first round
    svc1.save_controller(ckpt)

    # 'restart': fresh store, fresh clock, fresh service — then restore
    clk2 = ScriptedClock()
    store2 = UpdateStore(clock=clk2.clock)
    svc2 = _service(store2, clk2, adaptive=True)
    svc2.load_controller(ckpt)
    schedule(clk2, store2, 0.0)
    _, rep2 = svc2.aggregate(from_store=True, expected_clients=10,
                             async_round=True)
    assert rep2.close_policy.source == "learned"  # resumed, not re-learned
    assert rep2.n_clients == n
    assert rep2.monitor.waited < 3.0              # closes on the curve
    # non-adaptive services refuse (no controller to persist)
    plain = _service(UpdateStore())
    with pytest.raises(ValueError):
        plain.save_controller(str(tmp_path / "x"))
    with pytest.raises(ValueError):
        plain.load_controller(ckpt)
