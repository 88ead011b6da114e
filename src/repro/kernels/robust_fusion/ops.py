"""Public wrappers for the robust-fusion carve kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.robust_fusion.kernel import topk_carve_pallas
from repro.kernels.robust_fusion.ref import topk_carve_ref

__all__ = [
    "topk_carve_pallas",
    "topk_carve_ref",
    "carve_stream_dense",
]


def carve_stream_dense(updates, trim: int, *, chunk: int = 8,
                       interpret: bool):
    """Dense-parity harness: stream a dense (n, P) matrix through the
    carve kernel in (chunk, P) blocks and finalize. Must equal
    ``trimmedmean_ref(updates, trim)`` (trim = (n-1)//2 gives the
    median) — used by tests to pin the streamed path to the oracle."""
    n, p = updates.shape
    if not 2 * trim < n:
        raise ValueError(f"trim {trim} too large for n={n}")
    k_cap = max(trim, 1)
    ssum = jnp.zeros((p,), jnp.float32)
    topk = jnp.full((k_cap, p), -jnp.inf, jnp.float32)
    botk = jnp.full((k_cap, p), jnp.inf, jnp.float32)
    for i in range(0, n, chunk):
        blk = updates[i: i + chunk]
        rows = blk.shape[0]
        if rows < chunk:  # ragged tail: zero rows masked out by valid
            blk = jnp.pad(blk, ((0, chunk - rows), (0, 0)))
        valid = (jnp.arange(chunk) < rows).astype(jnp.float32)
        ssum, topk, botk = topk_carve_pallas(blk, valid, ssum, topk, botk,
                                             interpret=interpret)
    s = ssum
    if trim > 0:
        s = s - jnp.sum(topk[k_cap - trim:], axis=0)
        s = s - jnp.sum(botk[:trim], axis=0)
    return s / float(n - 2 * trim)
