"""Averaging-family fusion algorithms (paper §III-A: 'averaging is the
common building block of most fusion algorithms').

FedAvg  — Eq. (1): M = sum_i w_i * u_i / (n_total + eps), w_i = sample
          counts (IBMFL FedAvgFusionHandler semantics).
IterAvg — unweighted mean (IBMFL IterAvgFusionHandler).
GradAvg — weighted gradient mean (server applies it as a gradient).
ClippedAvg — per-update L2 clip to a threshold, then FedAvg.
FedAvgM/server-momentum and FedAdam live in serveropt.py.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.fusion.base import EPS, FusionAlgorithm

# fp32 products: the TPU's default matmul precision contracts fp32
# operands as bf16, about 3 significant digits in the fused model
_F32 = jax.lax.Precision.HIGHEST


class FedAvg(FusionAlgorithm):
    name = "fedavg"
    reducible = True

    def fuse(self, updates, weights):
        wsum, tot = self.partial(updates, weights)
        return self.combine(wsum, tot)

    def partial(self, updates, weights):
        w = weights.astype(jnp.float32)
        wsum = jnp.einsum("np,n->p", updates.astype(jnp.float32), w,
                          precision=_F32)
        return wsum, jnp.sum(w)

    def combine(self, weighted_sum, weight_sum):
        return weighted_sum / (weight_sum + EPS)


class IterAvg(FusionAlgorithm):
    """Unweighted mean. ``effective_weights`` maps everything to 1 so the
    reduction is pad-safe (padded rows carry weight 0)."""

    name = "iteravg"
    reducible = True

    def effective_weights(self, weights):
        return jnp.ones_like(jnp.asarray(weights, jnp.float32))

    def fuse(self, updates, weights):
        w = self.effective_weights(
            weights if weights is not None
            else jnp.ones((updates.shape[0],), jnp.float32)
        )
        wsum, tot = self.partial(updates, w)
        return self.combine(wsum, tot)

    def partial(self, updates, weights):
        w = weights.astype(jnp.float32)
        return jnp.einsum(
            "np,n->p", updates.astype(jnp.float32), w, precision=_F32
        ), jnp.sum(w)

    def combine(self, weighted_sum, weight_sum):
        return weighted_sum / (weight_sum + EPS)


class GradAvg(FusionAlgorithm):
    """Same reduction as FedAvg; semantically the inputs are gradients and
    the server optimizer (optim/) applies the fused result."""

    name = "gradavg"
    reducible = True

    def fuse(self, updates, weights):
        wsum, tot = self.partial(updates, weights)
        return self.combine(wsum, tot)

    partial = FedAvg.partial
    combine = FedAvg.combine


@dataclasses.dataclass
class ClippedAvg(FusionAlgorithm):
    """L2-clip each update to ``clip_norm`` then weighted-average.
    Still reducible: the clip is per-client (map side)."""

    clip_norm: float = 10.0
    name = "clippedavg"
    reducible = True
    needs_row_norms = True  # the clip norm is over the FULL row

    def fuse(self, updates, weights):
        norms = jnp.linalg.norm(updates.astype(jnp.float32), axis=1)
        wsum, tot = self.partial_with_norms(updates, weights, norms)
        return self.combine(wsum, tot)

    def partial(self, updates, weights):
        # single-shard case: local norms ARE the full norms
        norms = jnp.linalg.norm(updates.astype(jnp.float32), axis=1)
        return self.partial_with_norms(updates, weights, norms)

    def partial_with_norms(self, updates, weights, row_norms):
        w = weights.astype(jnp.float32)
        scale = jnp.minimum(1.0, self.clip_norm / (row_norms + EPS))
        clipped = updates.astype(jnp.float32) * scale[:, None]
        return jnp.einsum("np,n->p", clipped, w,
                          precision=_F32), jnp.sum(w)

    def combine(self, weighted_sum, weight_sum):
        return weighted_sum / (weight_sum + EPS)
