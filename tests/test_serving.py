"""Serving-layer battery: the HTTP ingest front-end end to end.

The core claim under test is TRANSPORT TRANSPARENCY: a round fused
from socket-ingested updates is bit-identical to the same round fused
from in-process ``store.write`` calls — dense, compressed, and mixed.
Around it: every admission-control rejection path (401/400/413/429/503)
rejects WITHOUT landing anything, backpressure is explicit, and a
PR-8 ``WorkloadSpec`` trace replays over real sockets as the
multi-tenant smoke. The ``--quick`` ingest benchmark runs as a
subprocess gate at the end (mirrors test_soak.py's pattern).
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from repro.core import (
    AggregationService,
    FairRoundScheduler,
    UpdateStore,
)
from repro.core.compress import compress_update
from repro.serving import (
    BackpressureError,
    HttpStoreClient,
    IngestError,
    IngestQueue,
    IngestServer,
    encode_update,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOKENS = {"tok-a": "appa", "tok-b": "appb"}
CLIENT_TOKENS = {"appa": "tok-a", "appb": "tok-b"}


def _mk_service(store, timeout=5.0, **kw):
    return AggregationService(
        fusion="fedavg", local_strategy="jnp", store=store,
        threshold_frac=1.0, monitor_timeout=timeout, **kw,
    )


def _payloads(n, p, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(p,)).astype(np.float32) for _ in range(n)]


def _post_raw(port, body, token="tok-a", path="/v1/upload",
              content_length=None):
    """One raw POST, returning (status, headers, body)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method="POST",
        headers={"Authorization": f"Bearer {token}",
                 "Content-Type": "application/octet-stream"},
    )
    if content_length is not None:
        req.add_header("Content-Length", str(content_length))
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


# -- e2e exactness -----------------------------------------------------------

@pytest.mark.parametrize("mode", ["dense", "compressed", "mixed"])
def test_socket_round_bit_identical_to_inprocess(mode):
    """upload -> round == store.write -> round, bitwise, for dense,
    compressed, and mixed payload populations."""
    n, p = 6, 1500
    payloads = _payloads(n, p)

    def u_for(i, vec):
        if mode == "dense" or (mode == "mixed" and i % 2 == 0):
            return vec
        return compress_update(vec, block=256)

    # reference: in-process writes on a private store/service
    ref_store = UpdateStore()
    for i, vec in enumerate(payloads):
        ref_store.write(f"c{i}", u_for(i, vec), weight=1.0 + i,
                        tenant="appa")
    ref_fused, ref_rep = _mk_service(ref_store).aggregate(
        from_store=True, expected_clients=n, tenant="appa")

    # same updates over real sockets
    store = UpdateStore()
    svc = _mk_service(store)
    with IngestServer(store, TOKENS) as srv:
        cli = HttpStoreClient("127.0.0.1", srv.port,
                              tokens=CLIENT_TOKENS)
        for i, vec in enumerate(payloads):
            cli.write(f"c{i}", u_for(i, vec), weight=1.0 + i,
                      tenant="appa")
        fused, rep = svc.aggregate(from_store=True,
                                   expected_clients=n, tenant="appa")
    assert rep.n_clients == ref_rep.n_clients == n
    a, b = np.asarray(fused), np.asarray(ref_fused)
    assert a.dtype == b.dtype
    assert np.array_equal(a, b), "socket round diverged bitwise"


def test_upload_weights_and_bytes_land_exactly():
    store = UpdateStore()
    vec = np.arange(300, dtype=np.float32)
    with IngestServer(store, TOKENS) as srv:
        cli = HttpStoreClient("127.0.0.1", srv.port, token="tok-a")
        lat = cli.write("c0", vec, weight=3.5, tenant="appa")
        assert lat > 0   # the modeled store latency came back
        got, w = store.read("c0", tenant="appa")
        assert w == 3.5
        assert np.array_equal(np.asarray(got), vec)
        st = store.stats_for("appa")
        assert st.writes == 1
        assert st.bytes_written == vec.nbytes * store.replication


# -- auth / malformed / oversized: fail closed -------------------------------

def test_bad_token_is_401_and_lands_nothing():
    store = UpdateStore()
    with IngestServer(store, TOKENS) as srv:
        body = encode_update("c0", np.ones(8, np.float32))
        status, _, _ = _post_raw(srv.port, body, token="tok-nope")
        assert status == 401
        status, _, _ = _post_raw(srv.port, body, token="")
        assert status == 401
    assert store.count() == 0


def test_unknown_route_is_404():
    with IngestServer(UpdateStore(), TOKENS) as srv:
        status, _, _ = _post_raw(srv.port, b"x", path="/v1/nope")
        assert status == 404
        status = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/healthz", timeout=5
        ).status
        assert status == 200


@pytest.mark.parametrize("mangle", [
    lambda b: b[:-3],                      # truncated tail
    lambda b: b + b"\x00\x01",             # trailing garbage
    lambda b: b"XXXX" + b[4:],             # bad magic
    lambda b: b[:4] + b"\x07" + b[5:],     # unknown kind
    lambda b: b"",                         # empty body
])
def test_malformed_frame_is_400_and_lands_nothing(mangle):
    store = UpdateStore()
    good = encode_update("c0", np.ones(64, np.float32), weight=2.0)
    with IngestServer(store, TOKENS) as srv:
        status, _, body = _post_raw(srv.port, mangle(good))
        assert status == 400, body
        assert store.count() == 0
        # the connection / server stay usable after a reject
        status, _, _ = _post_raw(srv.port, good)
        assert status == 200
    assert store.count() == 1


def test_oversized_body_is_413_and_lands_nothing():
    store = UpdateStore()
    with IngestServer(store, TOKENS, max_body_bytes=1024) as srv:
        body = encode_update("c0", np.ones(4096, np.float32))
        status, _, _ = _post_raw(srv.port, body)
        assert status == 413
        assert srv.metrics().get("shed_413") == 1
    assert store.count() == 0


def test_missing_content_length_is_411():
    with IngestServer(UpdateStore(), TOKENS) as srv:
        # raw socket: POST with no Content-Length at all
        s = socket.create_connection(("127.0.0.1", srv.port),
                                     timeout=5)
        try:
            s.sendall(b"POST /v1/upload HTTP/1.1\r\n"
                      b"Host: x\r\nAuthorization: Bearer tok-a\r\n"
                      b"\r\n")
            resp = s.recv(4096)
        finally:
            s.close()
        assert b"411" in resp.split(b"\r\n", 1)[0]


# -- rate limiting / quotas --------------------------------------------------

def test_rate_limit_429_with_retry_after_and_no_partial_blob():
    store = UpdateStore()
    with IngestServer(store, TOKENS, rate=1e-3, burst=2.0) as srv:
        body = encode_update("c0", np.ones(32, np.float32))
        # burst=2 admits two, third sheds
        assert _post_raw(srv.port, body)[0] == 200
        assert _post_raw(srv.port,
                         encode_update("c1",
                                       np.ones(32, np.float32)))[0] \
            == 200
        status, headers, _ = _post_raw(
            srv.port, encode_update("c2", np.ones(32, np.float32)))
        assert status == 429
        assert float(headers["Retry-After"]) > 0
        # the shed upload landed NOTHING; the admitted two are intact
        assert store.count(tenant="appa") == 2
        assert sorted(store.client_ids(tenant="appa")) == ["c0", "c1"]
        # and rate limits are per tenant: appb is unaffected
        status, _, _ = _post_raw(
            srv.port, encode_update("b0", np.ones(32, np.float32)),
            token="tok-b")
        assert status == 200


def test_quota_429_never_lands_a_partial_blob(tmp_path):
    """Quota rejection on a DISK store: no orphan file, no index entry,
    byte accounting untouched."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.set_quota("appa", max_updates=2, policy="reject")
    with IngestServer(store, TOKENS) as srv:
        cli = HttpStoreClient("127.0.0.1", srv.port, token="tok-a",
                              max_attempts=2, sleep=lambda s: None)
        cli.write("c0", np.ones(64, np.float32), tenant="appa")
        cli.write("c1", np.ones(64, np.float32), tenant="appa")

        def spool_files():
            return sorted(
                os.path.join(r, f)
                for r, _, fs in os.walk(tmp_path) for f in fs
            )

        before = spool_files()
        bytes_before = store.tenant_bytes("appa")
        with pytest.raises(IngestError) as ei:
            cli.write("c2", np.ones(64, np.float32), tenant="appa")
        assert "429" in str(ei.value) or ei.value.status == 429
        assert store.count(tenant="appa") == 2
        assert store.tenant_bytes("appa") == bytes_before
        assert spool_files() == before, "429 left an orphan blob"
        assert srv.metrics().get("shed_429", 0) >= 1


def test_store_quota_reject_at_commit_time_is_429(tmp_path):
    """With the admission pre-check disabled, the store's own quota
    check at commit time is authoritative: it surfaces as the same 429,
    lands nothing — and, unlike the door pre-check, it KNOWS the
    client_id, so replacing a resident client at full count quota
    works."""
    from repro.serving import AdmissionController

    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.set_quota("appa", max_updates=2, policy="reject")
    admission = AdmissionController(TOKENS)   # no store: no pre-check
    with IngestServer(store, TOKENS, admission=admission) as srv:
        cli = HttpStoreClient("127.0.0.1", srv.port, token="tok-a",
                              max_attempts=2, sleep=lambda s: None)
        cli.write("c0", np.ones(64, np.float32), tenant="appa")
        cli.write("c1", np.ones(64, np.float32), tenant="appa")
        with pytest.raises(IngestError):
            cli.write("c2", np.ones(64, np.float32), tenant="appa")
        assert srv.metrics().get("quota_reject", 0) >= 1
        assert store.count(tenant="appa") == 2
        # replacement of a RESIDENT client still fits the count quota
        assert cli.write("c0", np.zeros(64, np.float32),
                         tenant="appa") > 0
        got, _ = store.read("c0", tenant="appa")
        assert not np.any(np.asarray(got))


# -- backpressure ------------------------------------------------------------

class _GatedStore:
    """Store proxy whose write_batch blocks on an Event — makes the
    committer hang so the IngestQueue saturates deterministically."""

    def __init__(self, store, gate):
        self._store = store
        self._gate = gate

    def write_batch(self, items):
        self._gate.wait(timeout=30)
        return self._store.write_batch(items)

    def __getattr__(self, name):
        return getattr(self._store, name)


def test_backpressure_503_when_queue_saturated():
    store = UpdateStore()
    gate = threading.Event()
    gated = _GatedStore(store, gate)
    q = IngestQueue(gated, maxsize=2, batch_max=2)
    with IngestServer(store, TOKENS, ingest_queue=q,
                      commit_timeout=30.0) as srv:
        # saturate deterministically: the committer picks up the first
        # submission (depth drains to 0), then two more fill the queue
        futs = [q.submit("h0", np.ones(16, np.float32))]
        deadline = time.time() + 5
        while q.depth() > 0 and time.time() < deadline:
            time.sleep(0.01)
        assert q.depth() == 0, "committer never picked up the head"
        futs.append(q.submit("h1", np.ones(16, np.float32)))
        futs.append(q.submit("h2", np.ones(16, np.float32)))
        assert q.depth() == 2
        # the front-end must now shed with 503 + Retry-After
        body = encode_update("c99", np.ones(16, np.float32))
        status, headers, _ = _post_raw(srv.port, body)
        assert status == 503
        assert float(headers["Retry-After"]) > 0
        assert srv.metrics().get("backpressure") == 1
        assert q.stats()["shed"] >= 1
        gate.set()           # release the committer; queued commits land
        for f in futs:
            assert f.result(timeout=10) > 0
        # and the SAME upload succeeds once pressure clears
        status, _, _ = _post_raw(srv.port, body)
        assert status == 200
    assert store.count() == 4
    assert "c99" in store.client_ids()
    assert sorted(store.client_ids()) == ["c99", "h0", "h1", "h2"]


def test_ingest_queue_backpressure_error_direct():
    gate = threading.Event()
    q = IngestQueue(_GatedStore(UpdateStore(), gate), maxsize=1,
                    batch_max=4)
    q.submit("a", np.ones(4, np.float32))
    deadline = time.time() + 5
    while q.depth() > 0 and time.time() < deadline:
        time.sleep(0.01)   # committer picked up the first
    q.submit("b", np.ones(4, np.float32))   # fills the queue
    with pytest.raises(BackpressureError) as ei:
        q.submit("c", np.ones(4, np.float32))
    assert ei.value.retry_after > 0
    gate.set()
    q.close()


# -- batched commits ---------------------------------------------------------

@pytest.mark.usefixtures("lock_witness")
def test_concurrent_uploads_coalesce_into_batches():
    store = UpdateStore()
    gate = threading.Event()
    q = IngestQueue(_GatedStore(store, gate), maxsize=64, batch_max=16)
    futs = [q.submit(f"c{i}", np.full(8, i, np.float32),
                     weight=1.0, tenant="appa") for i in range(12)]
    gate.set()
    for f in futs:
        assert f.result(timeout=10) > 0
    stats = q.stats()
    q.close()
    assert stats["committed"] == 12
    # the first submit may slip through alone, but the stalled rest
    # must coalesce: strictly fewer batches than updates
    assert stats["batches"] < 12
    assert stats["max_batch"] > 1
    assert store.count(tenant="appa") == 12


# -- fair scheduler ----------------------------------------------------------

class _FakeService:
    """Records aggregate() concurrency; no jax, no store."""

    def __init__(self):
        self.lock = threading.Lock()
        self.active = 0
        self.peak = 0
        self.calls = []
        self.store = None
        self.block = threading.Event()
        self.block.set()

    def aggregate(self, tenant=None, **kw):
        with self.lock:
            self.active += 1
            self.peak = max(self.peak, self.active)
            self.calls.append(tenant)
        self.block.wait(timeout=10)
        time.sleep(0.01)
        with self.lock:
            self.active -= 1
        # the scheduler adds its slot wait to the report's phases
        return (np.zeros(2), types.SimpleNamespace(phase_seconds={}))

    def _row_bytes(self, p, dtype):
        return p * 4

    def _chunk_rows(self, n, row_bytes):
        return n


def test_fair_scheduler_bounds_concurrency():
    svc = _FakeService()
    svc.block.clear()
    with FairRoundScheduler(svc, max_running=2) as sched:
        futs = [sched.submit(f"t{i}") for i in range(6)]
        deadline = time.time() + 5
        while len(sched.running()) < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert len(sched.running()) == 2
        svc.block.set()
        for f in futs:
            f.result(timeout=10)
    assert svc.peak <= 2
    assert sorted(svc.calls) == sorted(f"t{i}" for i in range(6))


def test_fair_scheduler_weighted_share():
    """Under contention (max_running=1, standing backlog) a weight-2
    tenant is admitted twice as often as a weight-1 tenant."""
    svc = _FakeService()
    sched = FairRoundScheduler(svc, max_running=1,
                               weights={"heavy": 2.0, "light": 1.0})
    try:
        futs = [sched.submit("heavy") for _ in range(8)] + \
               [sched.submit("light") for _ in range(4)]
        for f in futs:
            f.result(timeout=30)
        order = sched.admission_order()
        # every prefix of the admission order respects the 2:1 ratio
        # within WFQ's one-round tolerance
        for i in range(1, len(order) + 1):
            h = order[:i].count("heavy")
            l = order[:i].count("light")
            assert abs(h - 2 * l) <= 2, (
                f"2:1 share violated at prefix {i}: {order[:i]}")
    finally:
        sched.shutdown()


def test_fair_scheduler_same_tenant_rounds_serialize():
    svc = _FakeService()
    with FairRoundScheduler(svc, max_running=4) as sched:
        futs = [sched.submit("only") for _ in range(3)]
        for f in futs:
            f.result(timeout=10)
    assert svc.peak == 1   # one in flight per tenant, ever


def test_fair_scheduler_capacity_gate():
    """A tenant whose projected footprint busts capacity waits until
    the running set drains — but runs alone rather than deadlocking."""
    svc = _FakeService()

    class _Meta:
        def meta(self, tenant):
            return (4, 1000, np.float32)   # footprint 2*4*4000 = 32000

    svc.store = _Meta()
    svc.block.clear()
    with FairRoundScheduler(svc, max_running=2,
                            capacity_bytes=40_000) as sched:
        f1 = sched.submit("a")
        deadline = time.time() + 5
        while not sched.running() and time.time() < deadline:
            time.sleep(0.01)
        # b's 32000 + a's 32000 > 40000 -> b must wait despite a free
        # slot
        f2 = sched.submit("b")
        time.sleep(0.3)
        assert sched.running() == ["a"]
        assert sched.waiting().get("b") == 1
        svc.block.set()
        f1.result(timeout=10)
        f2.result(timeout=10)
    assert sorted(svc.calls) == ["a", "b"]


def test_upload_counters_rise_per_accepted_upload():
    """read_s / parse_s rise with each accepted upload only; the
    queue's queue_wait_s and commit_wait_s with each committed one."""
    store = UpdateStore()
    with IngestServer(store, TOKENS) as srv:
        m0 = srv.metrics()
        assert m0["read_s"] == m0["parse_s"] == 0.0
        assert m0["queue_wait_s"] == m0["commit_wait_s"] == 0.0
        cli = HttpStoreClient("127.0.0.1", srv.port, token="tok-a")
        for i, vec in enumerate(_payloads(3, 4096)):
            cli.write(f"c{i}", vec, tenant="appa")
        m3 = srv.metrics()
        status, _, _ = _post_raw(srv.port, b"FLU1 not a frame")
        assert status == 400
        m4 = srv.metrics()
    assert m3["accepted"] == 3 and m3["committed"] == 3
    assert m3["read_s"] > 0 and m3["parse_s"] > 0
    assert m3["queue_wait_s"] > 0 and m3["commit_wait_s"] > 0
    # a malformed frame is read and parsed, but not accepted: the sums
    # stay those of the accepted uploads
    assert m4["malformed"] == 1 and m4["accepted"] == 3
    for k in ("read_s", "parse_s", "queue_wait_s", "commit_wait_s"):
        assert m4[k] == m3[k], k


def test_ingest_queue_counts_the_wait_before_the_drain():
    """An upload queued behind a slow commit waits about that long."""

    class _SlowStore:
        def write_batch(self, items):
            time.sleep(0.2)
            return [0.0] * len(items)

    q = IngestQueue(_SlowStore(), batch_max=1)
    try:
        futs = [q.submit(f"c{i}", None) for i in range(2)]
        for f in futs:
            f.result(timeout=10)
        st = q.stats()
    finally:
        q.close()
    assert st["committed"] == 2 and st["batches"] == 2
    assert 0.4 <= st["commit_wait_s"] < 2.0   # two commits of 0.2 s
    assert 0.15 <= st["queue_wait_s"] < 2.0   # the second waited one


def test_fair_scheduler_counts_slot_wait():
    """slot_wait_s sums submit-to-admission over admitted rounds, and
    each report's phase_seconds["queue"] holds its own wait."""
    svc = _FakeService()
    svc.block.clear()
    with FairRoundScheduler(svc, max_running=1) as sched:
        f1 = sched.submit("a")
        f2 = sched.submit("b")   # waits for a's slot
        time.sleep(0.3)
        assert sched.stats()["running"] == 1
        svc.block.set()
        (_, r1), (_, r2) = f1.result(timeout=10), f2.result(timeout=10)
        st = sched.stats()
    assert st["admitted"] == 2 and st["running"] == 0
    assert r1.phase_seconds["queue"] < 0.2
    assert r2.phase_seconds["queue"] >= 0.3
    assert st["slot_wait_s"] == pytest.approx(
        r1.phase_seconds["queue"] + r2.phase_seconds["queue"])


# -- trace-replayed multi-tenant smoke (the tier-1 gate) ---------------------

@pytest.mark.usefixtures("lock_witness")
def test_trace_replayed_multitenant_smoke():
    """PR 8's WorkloadSpec driving the serving stack: K tenants replay
    a seeded trace over real sockets, rounds run through the fair
    scheduler, and every tenant's fused vector matches the formula."""
    from repro.fl import EdgeAggregatorServer
    from repro.workload import (
        FixedSize, RegimeSchedule, UniformArrivals, WorkloadSpec,
        start_writer, trace_payload,
    )

    k, n, p, seed = 3, 8, 600, 7
    spec = WorkloadSpec(
        tenants=tuple(f"app{i}" for i in range(k)),
        n_clients=n, rounds=1,
        regimes=RegimeSchedule.single(UniformArrivals(spread=0.2)),
        sizes=FixedSize(dim=p),
    )
    trace = spec.build(seed)
    tenants = [tr.tenant for tr in trace.rounds[0].tenants]
    tokens = {f"tok-{t}": t for t in tenants}
    store = UpdateStore()
    svc = _mk_service(store, timeout=20.0)
    with EdgeAggregatorServer(svc, tokens, max_running=2) as edge:
        writers = [
            start_writer(
                None, tr, seed,
                writer=HttpStoreClient(
                    "127.0.0.1", edge.port, token=f"tok-{tr.tenant}"
                ).write,
            )
            for tr in trace.rounds[0].tenants
        ]
        results = edge.run_rounds(tenants, expected_clients=n)
        for w in writers:
            w.join(timeout=30)
    for tr in trace.rounds[0].tenants:
        fused, rep = results[tr.tenant]
        assert rep.n_clients == n
        u = np.stack([trace_payload(seed, tr.tenant, ev.client_id, p)
                      for ev in tr.events])
        w = np.asarray([ev.weight for ev in tr.events], np.float32)
        ref = np.einsum("np,n->p", u, w) / (w.sum() + 1e-6)
        assert np.allclose(np.asarray(fused), ref, rtol=1e-5,
                           atol=1e-5), tr.tenant
    assert len(edge.scheduler.admission_order()) == k
    m = edge.metrics()
    assert m["rounds_admitted"] == k and m["rounds_running"] == 0
    assert m["slot_wait_s"] >= 0.0
    assert m["accepted"] == k * n
    assert all(results[t][1].phase_seconds["queue"] >= 0.0
               for t in tenants)


# -- benchmark smoke (tier-1 wiring) -----------------------------------------

def test_ingest_benchmark_quick_smoke(tmp_path):
    """The --quick ingest bench must hold its full acceptance bundle:
    every upload lands exactly once under mid-run disconnects, rounds
    are formula-exact, p50/p99 are reported."""
    out = tmp_path / "BENCH_ingest.json"
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "ingest_service.py"),
         "--quick", "--out", str(out)],
        capture_output=True, text=True, timeout=280,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(out.read_text())
    assert payload["acceptance"] is True, payload
    up = payload["uploads"]
    assert up["accepted"] == up["total"]
    assert up["disconnects_injected"] > 0
    assert 0 < up["p50_latency_s"] <= up["p99_latency_s"]
    assert all(payload["rounds_exact"].values())
