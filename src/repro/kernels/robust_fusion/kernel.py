"""Pallas TPU kernel: the streamed top-k carve for exact trimmed mean /
coordinate-wise median.

The carry per parameter tile is a running column sum plus the K largest
(``topk``) and K smallest (``botk``) values seen per coordinate, both
ascending along the K axis. Each grid step loads one (c, TP) strip of a
block and inserts its rows one at a time, without a sort (Mosaic has no
sort lowering): inserting x into the ascending top-K t keeps

    t'[k] = max(t[k], min(x, t[k+1]))      (t[K] = +inf)

and into the ascending bottom-K b keeps

    b'[k] = min(b[k], max(x, b[k-1]))      (b[-1] = -inf)

— compare-exchanges on whole (K, TP) tiles, O(c * K) per coordinate.
Padding rows (validity 0) enter as -inf / +inf and never survive; their
sum contribution is masked to 0.

The parameter axis is independent per column, so a ragged final tile
needs no host-side pad: columns past P compute on unspecified VMEM and
their writes are dropped.

Dense (non-streamed) order statistics do not use this kernel: at the
median K = (n-1)//2, and the O(n * K) insertion loses to XLA's sort —
``LocalEngine.fuse`` runs the fusion's own sort for them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PARAM_TILE = 1024


def _shifted(t, fill, up: bool):
    """Rows of ``t`` moved one place (up: row k <- row k+1), with the
    vacated row set to ``fill``."""
    edge = jnp.full_like(t[:1], fill)
    if t.shape[0] == 1:
        return edge
    if up:
        return jnp.concatenate([t[1:], edge], axis=0)
    return jnp.concatenate([edge, t[:-1]], axis=0)


def _carve_kernel(v_ref, u_ref, s_ref, t_ref, b_ref,
                  so_ref, to_ref, bo_ref, x_ref):
    """v: (c,) SMEM validity; u: (c, TP); s: (1, TP) running sum;
    t / b: (K, TP) ascending top-K / bottom-K. Outputs mirror s, t, b.
    x: (c, TP) fp32 scratch — the strip upcast once, because Mosaic
    loads single rows at a dynamic index only from 32-bit arrays."""
    so_ref[...] = s_ref[...]
    to_ref[...] = t_ref[...]
    bo_ref[...] = b_ref[...]
    x_ref[...] = u_ref[...].astype(jnp.float32)

    def insert(r, carry):
        x = x_ref[pl.ds(r, 1), :]                           # (1, TP)
        ok = v_ref[r] > 0
        so_ref[...] += jnp.where(ok, x, 0.0)
        t = to_ref[...]
        hi = jnp.where(ok, x, -jnp.inf)
        to_ref[...] = jnp.maximum(
            t, jnp.minimum(hi, _shifted(t, jnp.inf, up=True)))
        b = bo_ref[...]
        lo = jnp.where(ok, x, jnp.inf)
        bo_ref[...] = jnp.minimum(
            b, jnp.maximum(lo, _shifted(b, -jnp.inf, up=False)))
        return carry

    jax.lax.fori_loop(0, u_ref.shape[0], insert, 0)


@functools.partial(jax.jit, static_argnames=("param_tile", "interpret"))
def topk_carve_pallas(block: jnp.ndarray, valid: jnp.ndarray,
                      ssum: jnp.ndarray, topk: jnp.ndarray,
                      botk: jnp.ndarray, *, param_tile: int = PARAM_TILE,
                      interpret: bool):
    """Streaming fold for exact trimmed mean / median: merge a (c, P)
    block into carry (ssum (P,), topk (K, P), botk (K, P)). ``valid``
    (c,) is 0/1 (0 = padded row). Returns the updated carry triple.
    ``interpret`` has no default: compiled on the TPU, the Pallas
    interpreter on the CPU (``LocalEngine`` derives it)."""
    c, P = block.shape
    k_cap = topk.shape[0]
    tp = min(param_tile, P)
    strip = pl.BlockSpec((1, tp), lambda i: (0, i))
    kbuf = pl.BlockSpec((k_cap, tp), lambda i: (0, i))
    so, to, bo = pl.pallas_call(
        _carve_kernel,
        grid=(pl.cdiv(P, tp),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((c, tp), lambda i: (0, i)),
            strip, kbuf, kbuf,
        ],
        out_specs=[strip, kbuf, kbuf],
        scratch_shapes=[pltpu.VMEM((c, tp), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((1, P), jnp.float32),
            jax.ShapeDtypeStruct((k_cap, P), jnp.float32),
            jax.ShapeDtypeStruct((k_cap, P), jnp.float32),
        ],
        interpret=interpret,
    )(valid.astype(jnp.float32), block, ssum.reshape(1, P), topk, botk)
    return so[0], to, bo
