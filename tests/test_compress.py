"""Quantized transport + error feedback (beyond-paper, core/compress.py)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compress import (
    BLOCK,
    CompressedBlock,
    CompressedUpdate,
    ErrorFeedbackCompressor,
    RowBlock,
    compress_update,
    compressed_bytes,
    compression_ratio,
    dequantize,
    quantize,
    stack_block,
)
from repro.core.fusion import FedAvg
from repro.core.local import LocalEngine
from repro.core.service import AggregationService
from repro.core.store import UpdateStore

RNG = np.random.default_rng(21)


def test_quantize_roundtrip_error_bounded():
    v = jnp.asarray(RNG.normal(size=(5000,)).astype(np.float32))
    q, s = quantize(v)
    back = dequantize(q, s)
    # error bounded by half a quantization step per block
    err = np.abs(np.asarray(back - v))
    step = np.repeat(np.asarray(s), 2048)[: v.shape[0]]
    assert (err <= step / 2 + 1e-7).all()


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 5000), seed=st.integers(0, 99))
def test_quantize_shapes_property(n, seed):
    r = np.random.default_rng(seed)
    v = jnp.asarray(r.normal(size=(n,)).astype(np.float32) * 10)
    q, s = quantize(v)
    assert q.shape == (n,) and q.dtype == jnp.int8
    back = dequantize(q, s)
    assert back.shape == (n,)
    assert np.isfinite(np.asarray(back)).all()


def test_error_feedback_compensates():
    """Mean of EF-compressed repeated updates converges to the true mean
    (the residual carries what quantization dropped)."""
    block = 256
    ef = ErrorFeedbackCompressor(block=block)
    true = jnp.asarray(RNG.normal(size=(1024,)).astype(np.float32) * 1e-3)
    acc = np.zeros(1024, np.float64)
    T = 30
    for t in range(T):
        q, s = ef.compress(0, true)
        acc += np.asarray(dequantize(q, s, block), np.float64)
    np.testing.assert_allclose(acc / T, np.asarray(true), atol=2e-5)


def test_compressed_fedavg_close_to_exact():
    n, p = 16, 4096
    u = RNG.normal(size=(n, p)).astype(np.float32)
    w = RNG.uniform(1, 10, size=(n,)).astype(np.float32)
    ef = ErrorFeedbackCompressor()
    deq = np.stack([
        np.asarray(dequantize(*ef.compress(i, jnp.asarray(u[i]))))
        for i in range(n)
    ])
    eng = LocalEngine(strategy="jnp")
    exact = np.asarray(eng.fuse(FedAvg(), u, w))
    approx = np.asarray(eng.fuse(FedAvg(), deq, w))
    scale = np.abs(u).max()
    assert np.abs(exact - approx).max() < scale / 127  # one q-step


def test_compression_ratio():
    assert 3.9 < compression_ratio(1 << 20) <= 4.0


# -- quantize contract --------------------------------------------------------


def test_quantize_all_zero_and_spike_blocks():
    """Degenerate blocks: an all-zero block must round-trip to exact
    zeros (scale floors at 1e-12, codes are 0), and a single-spike
    block must preserve the spike within half a step."""
    block = 128
    v = np.zeros(3 * block, np.float32)
    v[2 * block + 17] = 5.0          # spike in the last block only
    q, s = quantize(jnp.asarray(v), block=block)
    back = np.asarray(dequantize(q, s, block))
    assert (back[: 2 * block] == 0.0).all()
    assert abs(back[2 * block + 17] - 5.0) <= float(s[2]) / 2 + 1e-7


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 3000), seed=st.integers(0, 99))
def test_quantize_per_element_error_property(n, seed):
    """Per-element |dequant - x| <= scale/2 for every block, any length."""
    block = 256
    r = np.random.default_rng(seed)
    v = (r.normal(size=(n,)) * 10 ** r.uniform(-4, 2)).astype(np.float32)
    q, s = quantize(jnp.asarray(v), block=block)
    back = np.asarray(dequantize(q, s, block))
    step = np.repeat(np.asarray(s), block)[:n]
    assert (np.abs(back - v) <= step / 2 + 1e-6).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_quantize_low_precision_inputs_keep_fp32_scales(dtype):
    """bf16/fp16 updates quantize without silently changing the return
    contract: int8 codes + FP32 scales, always."""
    v = jnp.asarray(RNG.normal(size=(600,)).astype(np.float32)).astype(dtype)
    q, s = quantize(v, block=128)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    back = np.asarray(dequantize(q, s, 128))
    err = np.abs(back - np.asarray(v, np.float32))
    assert (err <= np.repeat(np.asarray(s), 128)[:600] / 2 + 1e-2).all()


def test_compressed_bytes_counts_padding_and_scales():
    """The byte model is the padded codes + the fp32 scale vector —
    what the spool actually holds (satellite 1: the padded final block
    and the scales were previously uncounted)."""
    assert compressed_bytes(2048, 2048) == 2048 + 4
    assert compressed_bytes(2049, 2048) == 2 * 2048 + 8   # padded block
    cu = compress_update(np.ones(2049, np.float32))
    assert cu.nbytes == compressed_bytes(2049, 2048)


# -- store round-trip ---------------------------------------------------------


def test_store_roundtrips_compressed_updates(tmp_path):
    """CompressedUpdates survive write -> read and write -> iter_chunks
    on BOTH backends, without the store ever holding fp32."""
    v = RNG.normal(size=(5003,)).astype(np.float32)
    cu = compress_update(v)
    for store in (
        UpdateStore(),
        UpdateStore(backend="disk", spool_dir=str(tmp_path)),
    ):
        store.write("c0", cu, weight=2.0)
        got, w = store.read("c0")
        assert isinstance(got, CompressedUpdate) and w == 2.0
        np.testing.assert_allclose(got.dequantize(), cu.dequantize())
        n, p, dtype = store.meta()
        assert (n, p, dtype) == (1, 5003, np.dtype(np.int8))
        blocks = list(store.iter_chunks(4))
        assert len(blocks) == 1
        block = blocks[0][0]
        assert isinstance(block, RowBlock) and block.compressed
        assert isinstance(stack_block(block), CompressedBlock)


def test_store_quota_counts_compressed_bytes():
    """Satellite bugfix: per-tenant byte accounting charges the real
    on-spool compressed size (codes + scales), not the logical fp32
    size."""
    p = 4096
    cu = compress_update(np.ones(p, np.float32))
    store = UpdateStore(replication=1)
    store.write("c0", cu, tenant="appA")
    assert store.tenant_bytes("appA") == cu.nbytes   # ~p + 8, NOT 4p
    assert store.tenant_bytes("appA") < p * 4 // 3   # ~4x under fp32
    # a quota sized for compressed payloads admits them
    store.set_quota("appB", max_bytes=3 * cu.nbytes, policy="reject")
    for i in range(3):
        store.write(f"c{i}", compress_update(np.ones(p, np.float32)),
                    tenant="appB")
    assert store.tenant_bytes("appB") == 3 * cu.nbytes


def test_mixed_round_through_engine():
    """One stream may mix compressed and dense rows (a straggler that
    skipped quantization): per-kind steps, ONE accumulator."""
    n, p = 9, 5000
    u = RNG.normal(size=(n, p)).astype(np.float32)
    store = UpdateStore()
    for i in range(n - 2):
        store.write(f"c{i}", compress_update(u[i]))
    store.write("c7", u[7])
    store.write("c8", u[8])
    eng = LocalEngine(strategy="jnp")
    fused, rep = eng.fuse_stream(FedAvg(), store.iter_chunks(4),
                                 chunk_rows=4)
    exact = u.mean(0)
    assert np.abs(np.asarray(fused) - exact).max() < np.abs(u).max() / 127
    assert rep.ingest_bytes == 7 * compressed_bytes(p) + 2 * p * 4


# -- service-level quantized transport ----------------------------------------


def test_service_compressed_round_and_ingest_bytes():
    """A compressed round streams codes+scales end to end; RoundReport
    counts the real ingest bytes at < 0.3x the dense round's (satellite
    5's CI assertion, equal n and P)."""
    n, p = 12, 100_000
    u = RNG.normal(size=(n, p)).astype(np.float32)
    exact = u.mean(0)

    store_d = UpdateStore()
    svc_d = AggregationService(local_strategy="jnp", store=store_d)
    for i in range(n):
        store_d.write(f"c{i}", u[i])
    fused_d, rep_d = svc_d.aggregate(from_store=True, expected_clients=n)
    assert rep_d.bytes_ingested == n * p * 4

    store_c = UpdateStore()
    svc_c = AggregationService(local_strategy="jnp", store=store_c,
                               compress=True)
    for i in range(n):
        store_c.write(f"c{i}", svc_c.compress_update(f"c{i}", u[i]))
    fused_c, rep_c = svc_c.aggregate(from_store=True, expected_clients=n)
    assert rep_c.streamed
    assert rep_c.bytes_ingested == n * compressed_bytes(p)
    assert rep_c.bytes_ingested < 0.3 * rep_d.bytes_ingested
    assert np.abs(np.asarray(fused_c) - exact).max() < np.abs(u).max() / 127
    np.testing.assert_allclose(np.asarray(fused_d), exact, rtol=1e-5,
                               atol=1e-5)


def test_service_compress_update_requires_flag():
    svc = AggregationService()
    with pytest.raises(ValueError):
        svc.compress_update("c0", np.ones(10, np.float32))


def test_ef_multi_round_convergence_matches_fedavg():
    """Satellite 3: with error feedback, the multi-round fused mean of
    compressed rounds tracks uncompressed FedAvg — per-round residuals
    carry instead of accumulating."""
    n, p, T = 6, 4096, 12
    svc = AggregationService(compress=True)
    rng = np.random.default_rng(4)
    sum_c = np.zeros(p, np.float64)
    sum_x = np.zeros(p, np.float64)
    for t in range(T):
        u = rng.normal(size=(n, p)).astype(np.float32) * 1e-2
        store = UpdateStore()
        svc.store = store
        for i in range(n):
            store.write(f"c{i}", svc.compress_update(f"c{i}", u[i]))
        fused, _ = svc.aggregate(from_store=True, expected_clients=n)
        sum_c += np.asarray(fused, np.float64)
        sum_x += u.mean(0)
        store.clear()
    # cumulative error stays at ONE round's quantization step, not T's
    one_step = 1e-2 * 5 / 127
    assert np.abs(sum_c - sum_x).max() < 2 * one_step
