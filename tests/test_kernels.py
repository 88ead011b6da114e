"""Per-kernel shape/dtype sweeps against the pure-jnp ref.py oracles
(interpret=True executes the kernel body on CPU; tests/test_tpu_compile.py
compiles the same kernels for the TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_fusion.kernel import (
    weighted_sum_dequant_pallas,
    weighted_sum_pallas,
)
from repro.kernels.fused_fusion.ops import (
    fedavg_fused,
    fedavg_fused_dequant,
    iteravg_fused,
)
from repro.kernels.fused_fusion.ref import (
    fedavg_ref,
    weighted_sum_dequant_ref,
    weighted_sum_ref,
)
from repro.core.fusion.robust import CoordMedian, TrimmedMean
from repro.core.local import LocalEngine
from repro.kernels.robust_fusion.ref import coordmedian_ref, trimmedmean_ref

RNG = np.random.default_rng(7)


# -- fused_fusion -------------------------------------------------------------


@pytest.mark.parametrize("n,p", [(1, 16), (3, 127), (8, 1024), (37, 5003),
                                 (65, 2048), (256, 301)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.float16])
def test_weighted_sum_shapes_dtypes(n, p, dtype):
    u = jnp.asarray(RNG.normal(size=(n, p)).astype(np.float32)).astype(dtype)
    w = jnp.asarray(RNG.uniform(1, 4, size=(n,)).astype(np.float32))
    out = weighted_sum_pallas(u, w, interpret=True)
    ref = weighted_sum_ref(u, w)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("pt,ct", [(128, 8), (512, 32), (2048, 256)])
def test_weighted_sum_tile_sweep(pt, ct):
    u = jnp.asarray(RNG.normal(size=(40, 700)).astype(np.float32))
    w = jnp.asarray(RNG.uniform(1, 4, size=(40,)).astype(np.float32))
    out = weighted_sum_pallas(u, w, param_tile=pt, client_tile=ct,
                              interpret=True)
    np.testing.assert_allclose(out, weighted_sum_ref(u, w), rtol=2e-5,
                               atol=1e-4)


def test_fedavg_iteravg_ops():
    u = jnp.asarray(RNG.normal(size=(9, 333)).astype(np.float32))
    w = jnp.asarray(RNG.uniform(1, 9, size=(9,)).astype(np.float32))
    np.testing.assert_allclose(fedavg_fused(u, w, interpret=True),
                               fedavg_ref(u, w), rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(
        iteravg_fused(u, interpret=True), np.asarray(u).mean(0),
        rtol=2e-5, atol=1e-5
    )


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 40), p=st.integers(1, 600), seed=st.integers(0, 999))
def test_weighted_sum_property(n, p, seed):
    r = np.random.default_rng(seed)
    u = jnp.asarray(r.normal(size=(n, p)).astype(np.float32))
    w = jnp.asarray(r.uniform(0, 3, size=(n,)).astype(np.float32))
    np.testing.assert_allclose(
        weighted_sum_pallas(u, w, interpret=True), weighted_sum_ref(u, w),
        rtol=1e-4, atol=1e-3,
    )


# -- fused_fusion: in-kernel dequant fold -------------------------------------


def _quantized(n, p, block, rng):
    """Random (codes, scales, weights) with Pq padded to the block."""
    n_blocks = -(-p // block)
    codes = rng.integers(-127, 128, size=(n, n_blocks * block),
                         dtype=np.int8)
    codes[:, p:] = 0
    scales = rng.uniform(1e-4, 1e-2, size=(n, n_blocks)).astype(np.float32)
    w = rng.uniform(1, 4, size=(n,)).astype(np.float32)
    return jnp.asarray(codes), jnp.asarray(scales), jnp.asarray(w)


@pytest.mark.parametrize("n,p,block", [
    (1, 128, 128),        # single client, single tile
    (5, 5003, 2048),      # ragged param dim, default block
    (37, 4096, 2048),     # multi-tile clients
    (65, 300, 128),       # ragged client tile + small block
    (256, 1024, 256),     # many clients
])
def test_weighted_sum_dequant_parity(n, p, block):
    q, s, w = _quantized(n, p, block, np.random.default_rng(n * 1000 + p))
    out = weighted_sum_dequant_pallas(q, s, w, block=block, interpret=True)
    ref = weighted_sum_dequant_ref(q, s, w, block=block)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=1e-4)


def test_weighted_sum_dequant_matches_dense_kernel():
    """Folding the scales in-kernel must equal dequantizing first and
    running the dense weighted-sum kernel."""
    rng = np.random.default_rng(3)
    q, s, w = _quantized(19, 6000, 2048, rng)
    blk = 2048
    nb = q.shape[1] // blk
    dense = (np.asarray(q, np.float32).reshape(19, nb, blk)
             * np.asarray(s)[:, :, None]).reshape(19, -1)
    np.testing.assert_allclose(
        weighted_sum_dequant_pallas(q, s, w, interpret=True),
        weighted_sum_pallas(jnp.asarray(dense), w, interpret=True),
        rtol=2e-5, atol=1e-4,
    )


def test_fedavg_fused_dequant_op():
    rng = np.random.default_rng(5)
    q, s, w = _quantized(9, 3000, 1024, rng)
    out = fedavg_fused_dequant(q, s, w, block=1024, interpret=True)
    ref = weighted_sum_dequant_ref(q, s, w, block=1024) / jnp.sum(w)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 40), nb=st.integers(1, 6), seed=st.integers(0, 999))
def test_weighted_sum_dequant_property(n, nb, seed):
    block = 128
    q, s, w = _quantized(n, nb * block, block, np.random.default_rng(seed))
    np.testing.assert_allclose(
        weighted_sum_dequant_pallas(q, s, w, block=block, interpret=True),
        weighted_sum_dequant_ref(q, s, w, block=block),
        rtol=1e-4, atol=1e-3,
    )


# -- robust_fusion: dense rounds on the pallas strategy -----------------------
# (the dense order statistics run the fusion's sort, not a kernel — see
# LocalEngine.fuse; the carve kernel is swept in test_robust_stream.py)


@pytest.mark.parametrize("n,p", [(3, 64), (8, 1025), (17, 4096), (33, 100)])
def test_coordmedian_sweep(n, p):
    u = jnp.asarray(RNG.normal(size=(n, p)).astype(np.float32))
    out = LocalEngine(strategy="pallas").fuse(CoordMedian(), u, None)
    np.testing.assert_allclose(out, coordmedian_ref(u), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n,trim", [(9, 0), (9, 2), (20, 5)])
def test_trimmedmean_sweep(n, trim):
    u = jnp.asarray(RNG.normal(size=(n, 513)).astype(np.float32))
    fusion = TrimmedMean(beta=trim / n)
    assert fusion.trim_count(n) == trim
    np.testing.assert_allclose(
        LocalEngine(strategy="pallas").fuse(fusion, u, None),
        trimmedmean_ref(u, trim), rtol=1e-5, atol=1e-5,
    )


# -- flash_attention ----------------------------------------------------------


@pytest.mark.parametrize("T,nq,nkv,hd", [
    (128, 4, 4, 64),    # MHA
    (128, 8, 2, 64),    # GQA 4:1
    (256, 4, 1, 128),   # MQA, bigger head
])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_sweep(T, nq, nkv, hd, window):
    B = 2
    q = jnp.asarray(RNG.normal(size=(B, T, nq, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, T, nkv, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, T, nkv, hd)).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=3e-5)


def test_flash_attention_bf16():
    B, T, nq, nkv, hd = 2, 128, 4, 2, 64
    mk = lambda s: jnp.asarray(
        RNG.normal(size=s).astype(np.float32)
    ).astype(jnp.bfloat16)
    q, k, v = mk((B, T, nq, hd)), mk((B, T, nkv, hd)), mk((B, T, nkv, hd))
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_flash_matches_model_blockwise():
    """The Pallas kernel and the model's pure-jnp blockwise path agree."""
    from repro.models.layers.attention import blockwise_attention

    B, T, nq, nkv, hd = 2, 256, 6, 2, 64
    q = jnp.asarray(RNG.normal(size=(B, T, nq, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, T, nkv, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, T, nkv, hd)).astype(np.float32))
    a = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    b = blockwise_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=3e-5)
