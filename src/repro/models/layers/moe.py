"""Mixture-of-Experts layer with three TPU-adapted execution paths.

``a2a`` (train/prefill under a mesh) — shard_map expert parallelism:
    tokens stay on their (data x model)-sharded devices; each device
    routes locally into per-expert capacity buffers, ``all_to_all`` over
    the model axis ships buffers to the expert owners, experts run as
    dense MXU matmuls, and a second all_to_all ships results back. This
    is the canonical TPU schedule (GShard/Switch); collective volume is
    ~2 x tokens x d_model instead of the TB-scale traffic XLA emits for a
    cross-axis scatter (measured in EXPERIMENTS.md §Perf).

``dense-mix`` (decode) — with one token per sequence the step is HBM-
    bandwidth-bound on weight reads, and nearly every expert is hit by
    some token in the batch, so computing ALL experts and mixing by the
    (top-k masked) gate costs no extra HBM traffic and removes every
    gather/scatter. Extra FLOPs are free under the bandwidth roof.

``scatter`` (no mesh: CPU smoke tests/examples) — static-capacity
    buffers via scatter/gather, O(n*k*d + E*C*d) memory.

All three compute the same function (tests assert equivalence up to
capacity drops).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers.mlp import MLPParams, init_mlp, mlp
from repro.models.sharding import current_rules, shard


class MoEParams(NamedTuple):
    router: jax.Array         # (d, E) fp32
    w_gate: jax.Array         # (E, d, ff)
    w_up: jax.Array           # (E, d, ff)
    w_down: jax.Array         # (E, ff, d)
    shared: Optional[MLPParams]  # fused shared experts (ff_shared = n_shared*ff)


def init_moe(key, d_model: int, d_ff: int, n_experts: int, n_shared: int,
             dtype) -> MoEParams:
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    mk = lambda k, shape, s: (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)
    return MoEParams(
        router=(jax.random.normal(kr, (d_model, n_experts), jnp.float32) * s_in),
        w_gate=mk(kg, (n_experts, d_model, d_ff), s_in),
        w_up=mk(ku, (n_experts, d_model, d_ff), s_in),
        w_down=mk(kd, (n_experts, d_ff, d_model), s_out),
        shared=(
            init_mlp(ks, d_model, n_shared * d_ff, dtype) if n_shared else None
        ),
    )


# ---------------------------------------------------------------------------
# routing helpers (shared by all paths)
# ---------------------------------------------------------------------------


def _route(xt: jax.Array, router: jax.Array, top_k: int):
    """xt (n, d) -> (gate_vals (n,k), gate_idx (n,k), probs (n,E))."""
    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return gate_vals, gate_idx, probs


def _aux_loss(probs: jax.Array, gate_idx: jax.Array, E: int) -> jax.Array:
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=1),
        axis=0,
    )
    return E * jnp.sum(me * ce)


def _positions_in_expert(flat_idx: jax.Array, E: int) -> jax.Array:
    """Rank of each assignment among same-expert assignments (sort-based,
    O(n*k) memory)."""
    nk = flat_idx.shape[0]
    order = jnp.argsort(flat_idx, stable=True)
    sorted_idx = flat_idx[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_idx].add(1)
    starts = jnp.cumsum(counts) - counts
    ranks_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[sorted_idx]
    return jnp.zeros((nk,), jnp.int32).at[order].set(ranks_sorted)


def _capacity(n_tok: int, top_k: int, E: int, cf: float) -> int:
    c = int(max(top_k * n_tok * cf / E, 8))
    c = min(c, n_tok * top_k)
    return -(-c // 8) * 8


def _dispatch_combine_local(xt, router, wg, wu, wd, top_k, cf):
    """The scatter-path kernel on LOCAL (or global, meshless) tokens."""
    n_tok, d = xt.shape
    E = router.shape[1]
    gate_vals, gate_idx, probs = _route(xt, router, top_k)
    capacity = _capacity(n_tok, top_k, E, cf)
    flat_idx = gate_idx.reshape(-1)
    pos = _positions_in_expert(flat_idx, E)
    keep = pos < capacity
    slot = jnp.where(keep, pos, capacity - 1)

    buf = jnp.zeros((E, capacity, d), xt.dtype)
    contrib = jnp.repeat(xt, top_k, axis=0) * keep[:, None].astype(xt.dtype)
    buf = buf.at[flat_idx, slot].add(contrib, mode="drop")

    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd)

    gathered = out_buf[flat_idx, slot]
    gathered = gathered * (
        gate_vals.reshape(-1)[:, None].astype(xt.dtype)
        * keep[:, None].astype(xt.dtype)
    )
    out = jnp.sum(gathered.reshape(n_tok, top_k, d), axis=1)
    return out, _aux_loss(probs, gate_idx, E)


# ---------------------------------------------------------------------------
# paths
# ---------------------------------------------------------------------------


def _moe_scatter(p, x, top_k, cf):
    B, T, d = x.shape
    out, aux = _dispatch_combine_local(
        x.reshape(B * T, d), p.router, p.w_gate, p.w_up, p.w_down, top_k, cf
    )
    if p.shared is not None:
        out = out + mlp(p.shared, x).reshape(B * T, d)
    return out.reshape(B, T, d), aux


def _moe_dense_mix(p, x, top_k):
    """Decode path: all experts, gate-masked mix."""
    B, T, d = x.shape
    E = p.router.shape[1]
    xt = x.reshape(B * T, d)
    gate_vals, gate_idx, probs = _route(xt, p.router, top_k)
    # dense gates (n, E): top-k renormalized, zero elsewhere
    gates = jnp.zeros((B * T, E), jnp.float32).at[
        jnp.arange(B * T)[:, None], gate_idx
    ].set(gate_vals)
    # match the FSDP'd weight layout on the contraction dim -> partial
    # dots + psum (n is tiny; gathering full expert weights would be huge)
    xt = shard(xt, None, "dmodel")
    g = jnp.einsum("nd,edf->nef", xt, p.w_gate)
    u = jnp.einsum("nd,edf->nef", xt, p.w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = shard(h, None, None, "dmodel")
    y = jnp.einsum("nef,efd->ned", h, p.w_down)
    out = jnp.einsum("ned,ne->nd", y, gates.astype(x.dtype))
    if p.shared is not None:
        out = out + mlp(p.shared, x).reshape(B * T, d)
    return out.reshape(B, T, d), _aux_loss(probs, gate_idx, E)


def _moe_a2a(p, x, top_k, cf, rules):
    """shard_map expert-parallel path (see module docstring)."""
    mesh = rules.mesh
    model_ax = "model"
    n_model = mesh.shape[model_ax]
    dp = rules.rules.get("batch")
    dp_spec = tuple(dp) if dp and len(dp) > 1 else (dp[0] if dp else None)
    seq_ax = rules.rules.get("seq")
    seq_spec = seq_ax[0] if seq_ax else None
    B, T, d = x.shape
    E = p.router.shape[1]
    E_loc = E // n_model

    x_spec = P(dp_spec, seq_spec, None)
    w_spec = P(model_ax, None, None)

    def local(x_loc, router, wg, wu, wd):
        bl, tl, _ = x_loc.shape
        n_loc = bl * tl
        xt = x_loc.reshape(n_loc, d)
        gate_vals, gate_idx, probs = _route(xt, router, top_k)
        capacity = _capacity(n_loc, top_k, E, cf)
        flat_idx = gate_idx.reshape(-1)
        pos = _positions_in_expert(flat_idx, E)
        keep = pos < capacity
        slot = jnp.where(keep, pos, capacity - 1)

        buf = jnp.zeros((E, capacity, d), xt.dtype)
        contrib = jnp.repeat(xt, top_k, axis=0) * keep[:, None].astype(
            xt.dtype
        )
        buf = buf.at[flat_idx, slot].add(contrib, mode="drop")

        # ship buffers to expert owners: (E, C, d) -> (E_loc, m*C, d)
        buf = jax.lax.all_to_all(
            buf, model_ax, split_axis=0, concat_axis=1, tiled=True
        )
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
        # ship results back: (E_loc, m*C, d) -> (E, C, d)
        out_buf = jax.lax.all_to_all(
            out_buf, model_ax, split_axis=1, concat_axis=0, tiled=True
        )
        gathered = out_buf[flat_idx, slot]
        gathered = gathered * (
            gate_vals.reshape(-1)[:, None].astype(xt.dtype)
            * keep[:, None].astype(xt.dtype)
        )
        out = jnp.sum(gathered.reshape(n_loc, top_k, d), axis=1)
        aux = _aux_loss(probs, gate_idx, E)
        aux = jax.lax.pmean(aux, mesh.axis_names)
        return out.reshape(bl, tl, d), aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    out, aux = fn(x, p.router, p.w_gate, p.w_up, p.w_down)
    if p.shared is not None:
        out = out + mlp(p.shared, x)
    return out, aux


def moe(
    p: MoEParams,
    x: jax.Array,              # (B, T, d)
    top_k: int,
    capacity_factor: float,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,T,d), aux load-balance loss scalar)."""
    B, T, d = x.shape
    E = p.router.shape[1]
    rules = current_rules()
    if T == 1:
        return _moe_dense_mix(p, x, top_k)
    if rules is not None and "model" in rules.mesh.axis_names:
        n_model = rules.mesh.shape["model"]
        seq_ok = rules.rules.get("seq") and T % n_model == 0
        if E % n_model == 0 and seq_ok:
            return _moe_a2a(p, x, top_k, capacity_factor, rules)
    return _moe_scatter(p, x, top_k, capacity_factor)
