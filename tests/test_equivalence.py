"""The §IV-C convergence invariant, extended to the async path: every
engine — and every ROUND MODE — computes the same fusion formula.

With staleness discounting disabled, a monitor-overlapped async round
over a fixed client set must be allclose to the synchronous streamed
result, which in turn matches the dense single-chip formula; the
distributed engine's per-shard streaming ingest must match its dense
map-reduce. Async arrival timing is made deterministic with an injected
clock whose ``sleep`` fires scheduled client writes."""
import bisect
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (
    AggregationService,
    DistributedEngine,
    LocalEngine,
    UpdateStore,
)
from repro.core.fusion import REGISTRY, get_fusion
from repro.launch.mesh import make_mesh

RNG = np.random.default_rng(23)

REDUCIBLE = sorted(
    name for name, cls in REGISTRY.items() if cls().reducible
)


class ScriptedClock:
    """Deterministic clock: ``sleep`` advances time and fires any writes
    scheduled to land inside the elapsed window — late arrivals during an
    in-flight stream, reproducibly."""

    def __init__(self):
        self.t = 0.0
        self._events = []   # sorted [(time, fn)]

    def at(self, t, fn):
        bisect.insort(self._events, (t, id(fn), fn))

    def clock(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds
        while self._events and self._events[0][0] <= self.t:
            _, _, fn = self._events.pop(0)
            fn()


def _mk(n, p):
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
    return u, w


def _service(store, clk, fusion="fedavg", **kw):
    kw.setdefault("threshold_frac", 1.0)
    kw.setdefault("monitor_timeout", 60.0)
    return AggregationService(
        fusion=fusion, local_strategy="jnp", store=store,
        clock=clk.clock, sleep=clk.sleep, **kw,
    )


# -- async round == sync streamed == dense ------------------------------------


@pytest.mark.parametrize("name", REDUCIBLE)
def test_async_round_matches_sync_streamed(name):
    """Fixed client set, arrivals spread over the monitor window, NO
    staleness discount: the overlapped round is allclose to the
    serialized streamed round and the dense formula."""
    n, p = 11, 301
    u, w = _mk(n, p)

    # dense reference and serialized streamed result
    dense = np.asarray(
        LocalEngine(strategy="jnp").fuse(get_fusion(name), u, w)
    )
    store_sync = UpdateStore()
    for i in range(n):
        store_sync.write(f"c{i:02d}", u[i], weight=float(w[i]))
    sync_svc = AggregationService(
        fusion=name, local_strategy="jnp", store=store_sync,
        monitor_timeout=1.0, memory_cap_bytes=3 * p * 4 * 2,
    )
    sync_fused, sync_rep = sync_svc.aggregate(
        from_store=True, expected_clients=n,
    )
    assert sync_rep.streamed and not sync_rep.async_round

    # overlapped round: client i lands at t = 0.05 * (i + 1)
    clk = ScriptedClock()
    store = UpdateStore()
    for i in range(n):
        clk.at(0.05 * (i + 1),
               lambda i=i: store.write(f"c{i:02d}", u[i], weight=float(w[i])))
    svc = _service(store, clk, fusion=name,
                   memory_cap_bytes=3 * p * 4 * 2)
    fused, rep = svc.aggregate(
        from_store=True, expected_clients=n, async_round=True,
    )
    assert rep.async_round and rep.streamed
    assert rep.monitor.ready and rep.n_clients == n
    assert rep.overlap_seconds > 0
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(sync_fused), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(np.asarray(fused), dense, rtol=1e-4,
                               atol=1e-5)
    assert store.count() == 0   # async rounds consume what they fold


# -- distributed per-shard streaming == dense ---------------------------------


@pytest.mark.parametrize("name", REDUCIBLE)
def test_distributed_stream_matches_dense(name):
    n, p, chunk = 13, 257, 4
    u, w = _mk(n, p)
    dense = np.asarray(
        LocalEngine(strategy="jnp").fuse(get_fusion(name), u, w)
    )
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = DistributedEngine(mesh=mesh)

    def blocks():
        for lo in range(0, n, chunk):
            yield u[lo:lo + chunk], w[lo:lo + chunk]

    streamed, rep = eng.fuse_stream(get_fusion(name), blocks())
    np.testing.assert_allclose(np.asarray(streamed), dense, rtol=1e-4,
                               atol=1e-5)
    assert rep.n_rows == n and rep.n_blocks == -(-n // chunk)
    assert rep.compile_seconds > 0.0   # cold
    streamed2, rep2 = eng.fuse_stream(get_fusion(name), blocks())
    assert rep2.compile_seconds == 0.0  # warm: cached shard_map step
    np.testing.assert_allclose(np.asarray(streamed2), dense, rtol=1e-4,
                               atol=1e-5)


def test_distributed_stream_accumulator_carry():
    """Carried partial sums split across two streams equal one stream."""
    n, p = 12, 130
    u, w = _mk(n, p)
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = DistributedEngine(mesh=mesh)
    f = get_fusion("fedavg")
    full, _ = eng.fuse_stream(f, [(u, w)])
    _, rep1 = eng.fuse_stream(f, [(u[:5], w[:5])])
    part2, _ = eng.fuse_stream(
        f, [(u[5:], w[5:])], init=(rep1.acc_wsum, rep1.acc_tot)
    )
    np.testing.assert_allclose(np.asarray(part2), np.asarray(full),
                               rtol=1e-5, atol=1e-6)


def test_distributed_stream_multidevice_subprocess():
    """8-device mesh: per-shard streamed ingest == dense map-reduce ==
    local. Forced host device counts only in the subprocess."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 "
            + os.environ.get("XLA_FLAGS", "")
        )
        import numpy as np
        from repro.core import DistributedEngine, LocalEngine
        from repro.core.fusion import get_fusion
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(7)
        n, p, chunk = 21, 266, 6
        u = rng.normal(size=(n, p)).astype(np.float32)
        w = rng.uniform(1, 5, size=(n,)).astype(np.float32)
        mesh = make_mesh((4, 2), ("data", "model"))
        eng = DistributedEngine(mesh=mesh)
        f = get_fusion("clippedavg")   # exercises the psum'd row norms
        dense = np.asarray(eng.fuse(f, u, w))
        local = np.asarray(LocalEngine(strategy="jnp").fuse(f, u, w))

        def blocks():
            for lo in range(0, n, chunk):
                yield u[lo:lo + chunk], w[lo:lo + chunk]

        streamed, rep = eng.fuse_stream(f, blocks())
        np.testing.assert_allclose(np.asarray(streamed), dense,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(streamed), local,
                                   rtol=1e-4, atol=1e-5)
        assert rep.n_rows == n
        print("OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout
