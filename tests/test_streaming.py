"""Streaming aggregation pipeline + shape-bucketed compile caches.

Covers the tentpole invariants:
  * streamed-chunk == dense-fuse for every reducible fusion at ragged
    sizes (n and P not tile multiples), both engine strategies;
  * a second round whose client count lands in the same power-of-two
    bucket triggers ZERO new jit traces (local dense, local stream, and
    the distributed engine's cached shard_map closures);
  * aggregating from the store never materializes the dense (n, P)
    matrix on the host — peak ingest allocation is O(chunk * P);
  * the pad-free Pallas kernel performs no jnp.pad copy on ragged shapes;
  * the store preserves stored dtype and stays consistent under
    concurrent writers.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AggregationService, LocalEngine, UpdateStore
from repro.core import local as local_engine
from repro.core.compress import compress_update, stack_block
from repro.core.distributed import DistributedEngine
from repro.core.fusion import REGISTRY, get_fusion
from repro.kernels.fused_fusion.kernel import weighted_sum_pallas
from repro.utils import jitcache
from repro.launch.mesh import make_mesh

RNG = np.random.default_rng(11)

REDUCIBLE = sorted(
    name for name, cls in REGISTRY.items() if cls().reducible
)


def _blocks(u, w, chunk):
    for lo in range(0, u.shape[0], chunk):
        yield u[lo:lo + chunk], w[lo:lo + chunk]


# -- streamed == dense --------------------------------------------------------


@pytest.mark.parametrize("name", REDUCIBLE)
@pytest.mark.parametrize("strategy", ["jnp", "pallas"])
@pytest.mark.parametrize("n,p,chunk", [(13, 257, 4), (7, 301, 7), (9, 33, 2)])
def test_stream_matches_dense_ragged(name, strategy, n, p, chunk):
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
    dense = np.asarray(LocalEngine(strategy="jnp").fuse(get_fusion(name), u, w))
    eng = LocalEngine(strategy=strategy)
    streamed, rep = eng.fuse_stream(get_fusion(name), _blocks(u, w, chunk))
    np.testing.assert_allclose(streamed, dense, rtol=1e-4, atol=1e-5)
    assert rep.n_rows == n and rep.chunk_rows == chunk
    assert rep.n_blocks == -(-n // chunk)


def test_stream_rejects_non_streamable():
    u = RNG.normal(size=(6, 16)).astype(np.float32)
    w = np.ones(6, np.float32)
    with pytest.raises(ValueError, match="not streamable"):
        LocalEngine().fuse_stream(get_fusion("krum"), _blocks(u, w, 2))


def test_carve_stream_needs_n_hint():
    """Order-statistic streams size their top-k carve buffers from the
    expected client count — without it the stream must refuse."""
    u = RNG.normal(size=(6, 16)).astype(np.float32)
    w = np.ones(6, np.float32)
    with pytest.raises(ValueError, match="n_hint"):
        LocalEngine().fuse_stream(get_fusion("coordmedian"), _blocks(u, w, 2))


def test_stream_bf16_blocks_match_fp32_reference():
    """The store keeps bf16 updates at 2 bytes; the streamed accumulator
    is still fp32."""
    n, p = 12, 515
    u32 = RNG.normal(size=(n, p)).astype(np.float32)
    u16 = np.asarray(jnp.asarray(u32).astype(jnp.bfloat16))
    w = RNG.uniform(1, 3, size=(n,)).astype(np.float32)
    fused, _ = LocalEngine().fuse_stream(
        get_fusion("fedavg"), _blocks(u16, w, 5)
    )
    ref = np.asarray(LocalEngine().fuse(get_fusion("fedavg"), u32, w))
    np.testing.assert_allclose(np.asarray(fused), ref, rtol=2e-2, atol=2e-2)
    assert np.asarray(fused).dtype == np.float32


# -- row staging: store rows reach the device with no host stack -------------


def _host_stacked(arrays, chunk):
    """The host-stacked reference for ``LocalEngine._place``: the rows
    stacked into one host array, a ragged block padded with zero rows."""
    out = np.zeros((chunk,) + arrays[0].shape, arrays[0].dtype)
    out[:len(arrays)] = np.stack(arrays)
    return out, 0


def _staging_case(case, spool):
    """(fusion, store, chunk, n_hint, init, gamma) for one staging case;
    ``gamma`` turns the blocks into a staleness-scaled async round."""
    p = 5000
    store = UpdateStore(backend="disk", spool_dir=spool) \
        if case == "disk_int8" else UpdateStore()
    fusion, chunk, n_hint = get_fusion("fedavg"), 4, None
    init = gamma = None
    n = {"one_row": 3, "ragged_1": 9, "ragged_2": 10,
         "ragged_3": 11}.get(case, 10)
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
    for i in range(n):
        row = u[i]
        if case in ("int8", "disk_int8") or (
                case == "mixed" and i % 3 != 2):
            row = compress_update(u[i])
        store.write(f"c{i:02d}", row, weight=float(w[i]))
    if case == "one_row":
        chunk = 1
    elif case == "trimmed_carve":
        fusion, n_hint = get_fusion("trimmedmean"), n
    elif case == "stale_async":
        gamma = 0.5
        init = (RNG.normal(size=(p,)).astype(np.float32), np.float32(3.0))
    return fusion, store, chunk, n_hint, init, gamma


def _staged_blocks(store, chunk, gamma):
    for i, (block, w) in enumerate(store.iter_chunks(chunk)):
        if gamma is None:
            yield block, w
        else:   # per-row staleness: gamma ** age, ages varying by row
            ages = (np.arange(block.rows) + i) % 3
            yield block, w, (gamma ** ages).astype(np.float32)


@pytest.mark.parametrize("case", [
    "dense_fp32", "int8", "mixed", "one_row", "ragged_1", "ragged_2",
    "ragged_3", "trimmed_carve", "stale_async", "disk_int8",
])
def test_row_staging_matches_host_stacked(case, monkeypatch, tmp_path):
    """Rows placed one by one and assembled on the device fold to exactly
    what the host-stacked, host-padded operand folds to: the same fused
    vector and the same carried state, bit for bit."""
    monkeypatch.setattr(local_engine, "_PLACE_MIN_ROW_BYTES", 0)
    fusion, store, chunk, n_hint, init, gamma = _staging_case(
        case, str(tmp_path))
    eng = LocalEngine(strategy="pallas")
    ref = LocalEngine(strategy="pallas")
    monkeypatch.setattr(ref, "_place", _host_stacked)
    got, rep = eng.fuse_stream(
        fusion, _staged_blocks(store, chunk, gamma), init=init,
        chunk_rows=chunk, n_hint=n_hint)
    want, rep_ref = ref.fuse_stream(
        fusion, _staged_blocks(store, chunk, gamma), init=init,
        chunk_rows=chunk, n_hint=n_hint)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(rep.acc_state) == len(rep_ref.acc_state)
    for a, b in zip(rep.acc_state, rep_ref.acc_state):
        np.testing.assert_array_equal(a, b)
    assert rep.rows_placed == rep.n_rows == store.count()
    assert rep.ingest_bytes == rep_ref.ingest_bytes


def test_row_staging_ragged_blocks_compile_nothing(monkeypatch):
    """Ragged final blocks of every size reuse the one assembly and the
    one step executable: the compile cache misses no more and nothing
    is traced after the first round, and every row reached the device
    with no host stack."""
    monkeypatch.setattr(local_engine, "_PLACE_MIN_ROW_BYTES", 0)
    eng = LocalEngine(strategy="pallas")
    f = get_fusion("fedavg")
    p, chunk = 3001, 4     # a width no other test assembles
    misses = traces = None
    for n in (8, 9, 10, 11, 5, 1):
        u = RNG.normal(size=(n, p)).astype(np.float32)
        w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
        store = UpdateStore()
        for i in range(n):
            store.write(f"c{i:02d}", u[i], weight=float(w[i]))
        fused, rep = eng.fuse_stream(f, store.iter_chunks(chunk),
                                     chunk_rows=chunk)
        ref = np.einsum("np,n->p", u, w) / (w.sum() + 1e-6)
        np.testing.assert_allclose(np.asarray(fused), ref, rtol=1e-4,
                                   atol=1e-5)
        assert rep.rows_placed == rep.n_rows == n
        if misses is None:
            misses, traces = eng.cache.misses, jitcache.trace_count()
            assert misses == 1
        assert eng.cache.misses == misses
        assert jitcache.trace_count() == traces
        assert rep.compile_seconds == 0.0 or n == 8


def test_stage_span_counts_placed_rows(tmp_path, monkeypatch):
    """A trace shows how often placement engaged: each
    ``repro.engine.stage`` span carries its block's ``rows_placed``."""
    import glob

    monkeypatch.setattr(local_engine, "_PLACE_MIN_ROW_BYTES", 0)
    store = UpdateStore()
    for i in range(7):
        store.write(f"c{i}", RNG.normal(size=(300,)).astype(np.float32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, rep = LocalEngine(strategy="jnp").fuse_stream(
            get_fusion("fedavg"), store.iter_chunks(4), chunk_rows=4)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    placed = [dict(ev.stats).get("rows_placed")
              for plane in data.planes for line in plane.lines
              for ev in line.events if ev.name == "repro.engine.stage"]
    assert sorted(placed) == [3, 4] and rep.rows_placed == 7


@pytest.mark.parametrize("p", [1000, 50_000])
def test_row_size_picks_the_staging(p):
    """Rows under ``_PLACE_MIN_ROW_BYTES`` are stacked on the host in one
    copy, larger ones placed one by one; both fold to the same bits."""
    chunk, n = 4, 7
    u = RNG.normal(size=(n, p)).astype(np.float32)
    store = UpdateStore()
    for i in range(n):
        store.write(f"c{i}", u[i], weight=float(i + 1))
    f = get_fusion("fedavg")
    got, rep = LocalEngine(strategy="jnp").fuse_stream(
        f, store.iter_chunks(chunk), chunk_rows=chunk)
    ref = LocalEngine(strategy="jnp")
    ref._place = _host_stacked
    want, _ = ref.fuse_stream(f, store.iter_chunks(chunk), chunk_rows=chunk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    small = p * 4 < local_engine._PLACE_MIN_ROW_BYTES
    assert rep.rows_placed == (0 if small else n)


def test_store_blocks_share_the_store_rows(monkeypatch):
    """The memory backend's blocks are the store's rows, not copies, and
    stay read-only; a row consumed by a racing ``remove`` mid-load is
    skipped without a crash."""
    p = 4100
    store = UpdateStore()
    dense = RNG.normal(size=(3, p)).astype(np.float32)
    for i in range(3):
        store.write(f"d{i}", dense[i])
        store.write(f"q{i}", compress_update(dense[i]))
    orig = UpdateStore._read_versioned
    raced = []

    def read_then_race(self, key):
        out = orig(self, key)
        if key == ("default", "d0") and not raced:
            self.remove(["d1"])       # a concurrent round consumes d1
            raced.append(True)
        return out

    monkeypatch.setattr(UpdateStore, "_read_versioned", read_then_race)
    with store._lock:
        keys = store._keys("default")
    loaded = []
    blocks = store._load_block(keys, keys_out=loaded)
    assert raced
    assert ("default", "d1") not in loaded and len(loaded) == 5
    (dblk, _, dkeys), (qblk, _, qkeys) = blocks
    assert not dblk.compressed and qblk.compressed
    assert [k for _, k in dkeys] == ["d0", "d2"]
    for block, ks in ((dblk, dkeys), (qblk, qkeys)):
        for arr, key in zip(block.arrays, ks):
            held = store._mem[key][0]
            held = held.codes if block.compressed else held
            assert np.shares_memory(arr, held)
            assert not arr.flags.writeable
    np.testing.assert_array_equal(stack_block(dblk), dense[[0, 2]])
    assert stack_block(qblk).codes.shape == (3, qblk.width)


# -- shape-bucketed cache: zero re-traces -------------------------------------


@pytest.mark.parametrize("strategy", ["jnp", "pallas"])
def test_dense_bucket_cache_no_retrace(strategy):
    """n=11 and n=13 share the 16-bucket: one executable, zero new traces
    on the second round."""
    eng = LocalEngine(strategy=strategy)
    f = get_fusion("fedavg")
    p = 515
    out = {}
    for n in (11, 13):
        u = RNG.normal(size=(n, p)).astype(np.float32)
        w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
        before = jitcache.trace_count()
        out[n] = np.asarray(eng.fuse(f, u, w))
        ref = np.einsum("np,n->p", u, w) / (w.sum() + 1e-6)
        np.testing.assert_allclose(out[n], ref, rtol=1e-4, atol=1e-5)
        if n == 11:
            assert jitcache.trace_count() > before  # cold: traced
            assert eng.last_compile_seconds > 0.0
        else:
            assert jitcache.trace_count() == before, "same-bucket re-trace"
            assert eng.last_compile_seconds == 0.0
    assert eng.is_warm(f, 16, p, np.float32)
    assert not eng.is_warm(f, 17, p, np.float32)  # next bucket is cold


def test_stream_step_cache_no_retrace():
    eng = LocalEngine(strategy="pallas")
    f = get_fusion("fedavg")
    n, p, chunk = 19, 257, 8
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
    eng.fuse_stream(f, _blocks(u, w, chunk))
    assert eng.is_warm_stream(f, chunk, p, np.float32)
    before = jitcache.trace_count()
    fused, rep = eng.fuse_stream(f, _blocks(u[:14], w[:14], chunk))
    assert jitcache.trace_count() == before
    assert rep.compile_seconds == 0.0
    ref = np.einsum("np,n->p", u[:14], w[:14]) / (w[:14].sum() + 1e-6)
    np.testing.assert_allclose(np.asarray(fused), ref, rtol=1e-4, atol=1e-5)


def test_distributed_bucket_cache_no_retrace():
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = DistributedEngine(mesh=mesh)
    f = get_fusion("fedavg")
    p = 257
    for i, n in enumerate((11, 13)):
        u = RNG.normal(size=(n, p)).astype(np.float32)
        w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
        before = jitcache.trace_count()
        fused = np.asarray(eng.fuse(f, u, w))
        ref = np.einsum("np,n->p", u, w) / (w.sum() + 1e-6)
        np.testing.assert_allclose(fused, ref, rtol=1e-4, atol=1e-5)
        if i:
            assert jitcache.trace_count() == before, "same-bucket re-trace"
    assert eng.is_warm(f, 16, p, np.float32)


def test_memory_capped_scan_cache_no_retrace():
    """The capped path is one scanned executable, reused across rounds."""
    f = get_fusion("fedavg")
    p = 100
    eng = LocalEngine(strategy="jnp", memory_cap_bytes=3 * p * 4)
    for i, n in enumerate((13, 15)):
        u = RNG.normal(size=(n, p)).astype(np.float32)
        w = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
        before = jitcache.trace_count()
        fused = np.asarray(eng.fuse(f, u, w))
        ref = np.einsum("np,n->p", u, w) / (w.sum() + 1e-6)
        np.testing.assert_allclose(fused, ref, rtol=1e-4, atol=1e-5)
        if i:
            assert jitcache.trace_count() == before


# -- pad-free pallas kernel ---------------------------------------------------


def test_pallas_ragged_no_full_matrix_pad():
    """Ragged (n, P) must be masked inside the kernel, not jnp.pad-copied.
    (The interpreter may pad single TILES at block boundaries — that's
    O(tile), fine; what must never happen is a pad of the whole matrix.)"""
    import traceback

    n, p = 29, 519
    u = jnp.asarray(RNG.normal(size=(n, p)).astype(np.float32))
    w = jnp.asarray(RNG.uniform(1, 4, size=(n,)).astype(np.float32))
    real_pad = jax.numpy.pad
    our_pads = []

    def spy_pad(operand, *args, **kwargs):
        # jax-internal pads (the interpreter pads blocks on CPU; real TPU
        # DMA clamps instead) are not ours — attribute by call site
        stack = "".join(traceback.format_stack(limit=12))
        if "repro/kernels" in stack or "repro/core" in stack:
            our_pads.append(np.shape(operand))
        return real_pad(operand, *args, **kwargs)

    with mock.patch.object(jax.numpy, "pad", side_effect=spy_pad):
        # fresh shape + tiles => forces a trace through the wsum path
        out = weighted_sum_pallas(u, w, param_tile=256, client_tile=8,
                                  interpret=True)
    assert not our_pads, f"kernel wrapper pad-copied: {our_pads}"
    ref = jnp.einsum("np,n->p", u, w)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# -- store: streaming reads, dtype, concurrency -------------------------------


def test_store_meta_and_dtype_preserved():
    store = UpdateStore()
    vec = np.asarray(jnp.asarray(
        RNG.normal(size=(64,)).astype(np.float32)
    ).astype(jnp.bfloat16))
    store.write("c0", vec)
    store.write("c1", vec)
    n, p, dtype = store.meta()
    assert (n, p) == (2, 64)
    assert dtype.itemsize == 2, "bf16 must not be upcast to fp32 (2x bytes)"
    assert store.read("c0")[0].dtype == vec.dtype


def test_store_iter_chunks_ragged_and_peak_tracking():
    store = UpdateStore()
    n, p, chunk = 11, 40, 4
    for i in range(n):
        store.write(f"c{i:02d}", RNG.normal(size=(p,)).astype(np.float32),
                    weight=float(i + 1))
    blocks = list(store.iter_chunks(chunk))
    assert [b.shape[0] for b, _ in blocks] == [4, 4, 3]
    stacked = np.concatenate([stack_block(b) for b, _ in blocks])
    ref, wref = store.read_stacked()
    np.testing.assert_array_equal(stacked, ref)
    np.testing.assert_array_equal(
        np.concatenate([w for _, w in blocks]), wref
    )
    # iter_chunks staged at most chunk rows at a time...
    assert min(b.nbytes for b, _ in blocks) <= chunk * p * 4
    # ...while read_stacked's dense block shows up in the peak tracker
    assert store.stats.peak_block_bytes == n * p * 4


def test_store_concurrent_writes_consistent():
    import threading

    store = UpdateStore()
    p = 256

    def writer(k):
        for i in range(25):
            store.write(f"w{k}-{i}", np.full(p, k * 100 + i, np.float32),
                        weight=float(k))

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert store.count() == 100
    assert store.stats.writes == 100
    u, w = store.read("w2-7")
    assert w == 2.0 and u[0] == 207.0


def test_store_disk_bf16_roundtrip(tmp_path):
    """np.save can't round-trip ml_dtypes (bf16 reloads as raw V2); the
    disk backend must spool raw bytes + a dtype sidecar."""
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    vec = np.asarray(jnp.asarray(
        RNG.normal(size=(33,)).astype(np.float32)
    ).astype(jnp.bfloat16))
    store.write("b0", vec, weight=1.5)
    u, w = store.read("b0")
    assert u.dtype == vec.dtype and w == 1.5
    np.testing.assert_array_equal(u, vec)
    n, p, dtype = store.meta()
    assert (n, p) == (1, 33) and dtype == vec.dtype
    # jax must accept the reloaded block (V2 would raise)
    assert jnp.asarray(store.read_stacked()[0]).dtype == jnp.bfloat16
    # overwriting with fp32 clears the stale dtype sidecar
    store.write("b0", np.ones(33, np.float32))
    assert store.read("b0")[0].dtype == np.float32


def test_store_iter_chunks_abandoned_consumer_releases_reader():
    """Dropping the generator mid-stream must not leave the prefetch
    thread blocked holding staged blocks."""
    import threading

    store = UpdateStore()
    for i in range(20):
        store.write(f"c{i:02d}", np.zeros(64, np.float32))
    before = threading.active_count()
    it = store.iter_chunks(2)
    next(it)          # reader now staging/blocked on the full queue
    it.close()        # abandon: GeneratorExit runs the finally
    assert threading.active_count() == before


def test_store_disk_write_outside_lock_roundtrip(tmp_path):
    store = UpdateStore(backend="disk", spool_dir=str(tmp_path))
    store.write("a", np.arange(8, dtype=np.float32), weight=2.5)
    u, w = store.read("a")
    assert w == 2.5
    np.testing.assert_array_equal(u, np.arange(8, dtype=np.float32))
    n, p, dtype = store.meta()
    assert (n, p, dtype) == (1, 8, np.dtype(np.float32))


# -- service: zero-materialization round --------------------------------------


def test_service_store_round_streams_without_dense_read():
    n, p = 32, 1000
    store = UpdateStore()
    updates = RNG.normal(size=(n, p)).astype(np.float32)
    weights = RNG.uniform(1, 5, size=(n,)).astype(np.float32)
    for i in range(n):
        store.write(f"c{i:02d}", updates[i], weight=float(weights[i]))
    row = p * 4
    svc = AggregationService(
        fusion="fedavg", local_strategy="jnp", store=store,
        monitor_timeout=0.5, memory_cap_bytes=8 * row,  # chunk = 4 rows
    )
    with mock.patch.object(
        UpdateStore, "read_stacked",
        side_effect=AssertionError("dense (n, P) host read in stream path"),
    ):
        fused, rep = svc.aggregate(from_store=True, expected_clients=n)
    assert rep.streamed
    assert set(rep.phase_seconds) == {"ingest", "compile", "compute"}
    assert rep.phase_seconds["compile"] > 0.0  # cold first round
    # peak host ingest block is O(chunk * P), not O(n * P)
    assert store.stats.peak_block_bytes <= 4 * row
    manual = np.einsum("np,n->p", updates, weights) / (weights.sum() + 1e-6)
    np.testing.assert_allclose(np.asarray(fused), manual, rtol=1e-4,
                               atol=1e-4)
    # second elastic round, fewer clients, same chunk: warm executable
    store.clear()
    for i in range(n - 5):
        store.write(f"c{i:02d}", updates[i], weight=float(weights[i]))
    before = jitcache.trace_count()
    _, rep2 = svc.aggregate(from_store=True, expected_clients=n - 5)
    assert rep2.streamed
    assert rep2.phase_seconds["compile"] == 0.0
    assert jitcache.trace_count() == before, "warm round re-traced"


def test_service_streams_order_statistics_off_the_store():
    """Order-statistic fusions now stream off the store through the
    top-k carve (PR 7) — bit-matching the dense median."""
    n, p = 10, 64
    store = UpdateStore()
    updates = RNG.normal(size=(n, p)).astype(np.float32)
    for i in range(n):
        store.write(f"c{i}", updates[i])
    svc = AggregationService(fusion="coordmedian", local_strategy="jnp",
                             store=store, monitor_timeout=0.5)
    fused, rep = svc.aggregate(from_store=True, expected_clients=n)
    assert rep.streamed and not rep.notes
    np.testing.assert_allclose(
        np.asarray(fused), np.median(updates, axis=0), rtol=1e-5, atol=1e-6
    )


def test_service_dense_fallback_over_state_budget():
    """A carve whose O(K*P) state exceeds robust_state_budget routes to
    the dense path with an operator note instead of raising."""
    n, p = 10, 64
    store = UpdateStore()
    updates = RNG.normal(size=(n, p)).astype(np.float32)
    for i in range(n):
        store.write(f"c{i}", updates[i])
    svc = AggregationService(fusion="coordmedian", local_strategy="jnp",
                             store=store, monitor_timeout=0.5,
                             robust_state_budget=128)
    fused, rep = svc.aggregate(from_store=True, expected_clients=n)
    assert not rep.streamed
    assert rep.notes and "budget" in rep.notes[0]
    np.testing.assert_allclose(
        np.asarray(fused), np.median(updates, axis=0), rtol=1e-5, atol=1e-6
    )


# -- planner reuse term -------------------------------------------------------


def test_planner_reuse_term_prefers_warm_engine():
    from repro.core import Planner, Workload

    planner = Planner(n_devices=1)
    f = get_fusion("fedavg")
    load = Workload(update_bytes=1 << 20, n_clients=16)
    cold = planner.plan(load, f)
    warm = planner.plan(load, f, warm_engines={"local"})
    assert cold.breakdown["compile"] == planner.compile_overhead
    assert warm.breakdown["compile"] == 0.0
    assert warm.est_seconds < cold.est_seconds
