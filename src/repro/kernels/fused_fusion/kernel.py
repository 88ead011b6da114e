"""Pallas TPU kernel: streaming weighted-sum fusion.

The TPU adaptation of the paper's Numba single-node path. The (n, P)
update matrix streams through VMEM in (CLIENT_TILE x PARAM_TILE) blocks;
each parameter tile's fp32 accumulator lives in the output VMEM block and
is revisited across the client-tile grid dimension — one HBM pass over the
updates, one HBM write of the result, MXU-shaped (the inner op is a
(1, TN) x (TN, TP) matmul).

Ragged shapes are handled INSIDE the kernel: the final client/param tile
is masked with an iota row test instead of `jnp.pad`-copying the entire
updates matrix (the seed behavior, which doubled HBM traffic and peak
memory exactly when the matrix was largest). Boundary blocks' padding
lanes have unspecified contents, so the mask zeroes both the weight lane
and the update rows before the dot — 0 * garbage would still poison the
accumulator if the garbage were NaN/Inf.

Grid: (ceil(P / PARAM_TILE), ceil(n / CLIENT_TILE)); the output block
index ignores the client dim, so Pallas keeps it resident in VMEM across
that dim.

``interpret`` has no default: the caller says whether the kernel compiles
for the TPU or runs in the Pallas interpreter (CPU). ``LocalEngine``
derives it from the backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils.jitcache import note_trace

# lane-aligned defaults: PARAM_TILE a multiple of 128 (lanes), CLIENT_TILE
# a multiple of 8 (sublanes). VMEM budget @ defaults:
# 256*2048*4 B (updates tile) + 2048*4 (acc) ~= 2.1 MiB.
PARAM_TILE = 2048
CLIENT_TILE = 256

# pin fp32 products on the MXU: at the default precision the TPU may
# contract fp32 operands as bf16, about 3 significant digits
_F32 = jax.lax.Precision.HIGHEST


def _wsum_kernel(w_ref, u_ref, out_ref, *, n_rows, tn, ragged):
    """w: (1, TN) fp32; u: (TN, TP); out: (1, TP) fp32 accumulator."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...].astype(jnp.float32)
    w = w_ref[...]
    if ragged:
        # rows valid in this client tile: tn everywhere except the last
        valid = n_rows - j * tn
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
        w = jnp.where(ids < valid, w, 0.0)
        u = jnp.where(ids.reshape(tn, 1) < valid, u, 0.0)
    out_ref[...] += jnp.dot(w, u, preferred_element_type=jnp.float32,
                            precision=_F32)


@functools.partial(
    jax.jit, static_argnames=("param_tile", "client_tile", "interpret")
)
def weighted_sum_pallas(
    updates: jnp.ndarray,        # (n, P) any float dtype
    weights: jnp.ndarray,        # (n,) fp32
    *,
    param_tile: int = PARAM_TILE,
    client_tile: int = CLIENT_TILE,
    interpret: bool,
) -> jnp.ndarray:
    note_trace()
    n, P = updates.shape
    tn = min(client_tile, n)
    tp = min(param_tile, P)
    w2 = weights.astype(jnp.float32).reshape(1, n)

    kernel = functools.partial(
        _wsum_kernel, n_rows=n, tn=tn, ragged=bool(n % tn),
    )
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(P, tp), pl.cdiv(n, tn)),
        in_specs=[
            pl.BlockSpec((1, tn), lambda i, j: (0, j)),
            pl.BlockSpec((tn, tp), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, tp), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, P), jnp.float32),
        interpret=interpret,
    )(w2, updates)
    return out[0]


def _wsum_dequant_kernel(w_ref, s_ref, q_ref, out_ref, *, n_rows, tn,
                         ragged):
    """w: (1, TN) fp32; s: (1, TN) fp32 — each row's scale for THIS
    parameter tile, which is exactly one quantization block; q: (TN, blk)
    int8; out: (1, blk) fp32 accumulator.

    Dequantization is folded into the weighted sum: with one scale per
    row per tile, w[i] * s[i] * q[i, p] is a (1, TN) x (TN, blk) dot of
    the scaled weight row against the upcast int8 tile — the fp32 update
    matrix never exists in HBM. Ragged client tiles mask both the weight
    lane and the code rows (lanes past n_rows are unspecified VMEM, so
    0 * garbage could still be NaN)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = q_ref[...].astype(jnp.float32)          # (tn, blk)
    w = w_ref[...] * s_ref[...]
    if ragged:
        valid = n_rows - j * tn
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
        w = jnp.where(ids < valid, w, 0.0)
        q = jnp.where(ids.reshape(tn, 1) < valid, q, 0.0)
    out_ref[...] += jnp.dot(w, q, preferred_element_type=jnp.float32,
                            precision=_F32)


@functools.partial(
    jax.jit, static_argnames=("block", "client_tile", "interpret")
)
def weighted_sum_dequant_pallas(
    codes: jnp.ndarray,          # (n, Pq) int8, Pq a multiple of block
    scales: jnp.ndarray,         # (n, Pq // block) fp32 per-block scales
    weights: jnp.ndarray,        # (n,) fp32
    *,
    block: int = 2048,           # quantization block (compress.BLOCK)
    client_tile: int = CLIENT_TILE,
    interpret: bool,
) -> jnp.ndarray:
    """Weighted sum of block-quantized rows with the dequant scales
    folded in-kernel: out[p] = sum_i w[i] * s[i, p//block] * q[i, p].

    The parameter tile is one quantization block, so ``block`` must be a
    multiple of 128 (the TPU lane width) for the compiled kernel. The
    scales enter transposed as (Pq // block, 1, n): each grid cell's
    (1, TN) scale row then has lane-aligned block dims, which a (TN, 1)
    slice of the (n, Pq // block) matrix does not.

    Returns the (Pq,) fp32 weighted sum over the PADDED parameter axis
    (codes past the logical dim are zero by the CompressedUpdate
    contract, so callers just slice [:dim])."""
    note_trace()
    n, Pq = codes.shape
    if Pq % block:
        raise ValueError(f"codes width {Pq} not a multiple of block {block}")
    nb = Pq // block
    tn = min(client_tile, n)
    w2 = weights.astype(jnp.float32).reshape(1, n)
    s3 = scales.astype(jnp.float32).T.reshape(nb, 1, n)

    kernel = functools.partial(
        _wsum_dequant_kernel, n_rows=n, tn=tn, ragged=bool(n % tn),
    )
    out = pl.pallas_call(
        kernel,
        grid=(nb, pl.cdiv(n, tn)),
        in_specs=[
            pl.BlockSpec((1, tn), lambda i, j: (0, j)),
            pl.BlockSpec((None, 1, tn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((tn, block), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Pq), jnp.float32),
        interpret=interpret,
    )(w2, s3, codes)
    return out[0]
