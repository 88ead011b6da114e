"""Jit'd public wrappers for the fused-fusion kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.fusion.base import EPS
from repro.kernels.fused_fusion.kernel import (
    weighted_sum_dequant_pallas,
    weighted_sum_pallas,
)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_fused(updates: jnp.ndarray, weights: jnp.ndarray, *,
                 interpret: bool) -> jnp.ndarray:
    """Paper Eq. (1) with the streaming Pallas weighted-sum."""
    wsum = weighted_sum_pallas(updates, weights, interpret=interpret)
    return wsum / (jnp.sum(weights.astype(jnp.float32)) + EPS)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fedavg_fused_dequant(codes: jnp.ndarray, scales: jnp.ndarray,
                         weights: jnp.ndarray, block: int = 2048, *,
                         interpret: bool) -> jnp.ndarray:
    """Paper Eq. (1) straight from int8 codes + fp32 per-block scales:
    dequantization folds into the weighted-sum kernel, so the fp32
    update matrix never materializes."""
    wsum = weighted_sum_dequant_pallas(codes, scales, weights, block=block,
                                       interpret=interpret)
    return wsum / (jnp.sum(weights.astype(jnp.float32)) + EPS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def iteravg_fused(updates: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    n = updates.shape[0]
    w = jnp.ones((n,), jnp.float32)
    return weighted_sum_pallas(updates, w, interpret=interpret) / (n + EPS)
