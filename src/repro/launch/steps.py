"""Step functions lowered by the dry-run and the real launchers.

Each factory returns (fn, in_specs, out_specs?) ready for
``jax.jit(fn, in_shardings=...)`` — the same functions drive the CPU
examples (trivial mesh) and the 512-chip dry-run.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, input_specs
from repro.models import build_model
from repro.models.base import Model
from repro.models.sharding import decode_rules, train_rules, use_rules
from repro.optim import Optimizer, adamw, apply_updates
from repro.launch.shardings import (
    batch_shardings,
    cache_shardings,
    param_shardings,
)

PyTree = Any


def make_optimizer(cfg: ModelConfig) -> Optimizer:
    return adamw(lr=3e-4, b1=0.9, b2=0.95, weight_decay=0.0)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def make_train_step(model: Model, optimizer: Optimizer, mesh: Optional[Mesh],
                    microbatches: int = 1):
    """``microbatches > 1`` = gradient accumulation: the global batch is
    scanned in m slices, cutting activation/attention transient memory by
    ~m at the cost of re-running the per-slice weight all-gathers m times
    (the usual FSDP microbatching trade — measured in §Perf)."""
    rules = train_rules(mesh) if mesh is not None else None

    def train_step(params, opt_state, step, batch):
        with use_rules(rules):
            if microbatches == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss, has_aux=True
                )(params, batch)
            else:
                mb = jax.tree_util.tree_map(
                    lambda a: a.reshape(
                        (microbatches, a.shape[0] // microbatches)
                        + a.shape[1:]
                    ),
                    batch,
                )

                def acc_step(carry, mbatch):
                    loss_acc, g_acc = carry
                    (l, _), g = jax.value_and_grad(
                        model.loss, has_aux=True
                    )(params, mbatch)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(jnp.float32), g_acc, g
                    )
                    return (loss_acc + l, g_acc), None

                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                (loss, grads), _ = jax.lax.scan(
                    acc_step, (jnp.zeros((), jnp.float32), g0), mb
                )
                loss = loss / microbatches
                grads = jax.tree_util.tree_map(
                    lambda g: g / microbatches, grads
                )
            ups, opt_state2 = optimizer.update(grads, opt_state, step, params)
            new_params = apply_updates(params, ups)
        return new_params, opt_state2, loss

    return train_step


def train_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                optimizer: Optimizer):
    """(arg ShapeDtypeStructs, arg NamedShardings) for train_step."""
    model = build_model(cfg)
    param_spec = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))
    )
    opt_spec = jax.eval_shape(lambda: optimizer.init(param_spec))
    batch_spec = input_specs(cfg, shape)
    p_sh = param_shardings(param_spec, mesh)
    o_sh = _mirror_opt_shardings(opt_spec, param_spec, p_sh, mesh)
    b_sh = batch_shardings(batch_spec, mesh)
    step_spec = jax.ShapeDtypeStruct((), jnp.int32)
    step_sh = NamedSharding(mesh, P())
    args = (param_spec, opt_spec, step_spec, batch_spec)
    shardings = (p_sh, o_sh, step_sh, b_sh)
    return model, args, shardings


def _mirror_opt_shardings(opt_spec, param_spec, param_sh, mesh):
    """Optimizer moments share their parameter's sharding."""
    flat_p, _ = jax.tree_util.tree_flatten(param_spec)
    flat_ps, _ = jax.tree_util.tree_flatten(param_sh)
    by_shape = {}
    for s, sh in zip(flat_p, flat_ps):
        by_shape.setdefault((s.shape), sh)

    def go(leaf):
        return by_shape.get(leaf.shape, NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(go, opt_spec)


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------


def make_prefill_step(model: Model, mesh: Optional[Mesh]):
    rules = train_rules(mesh) if mesh is not None else None

    def prefill_step(params, batch):
        with use_rules(rules):
            return model.prefill(params, batch)

    return prefill_step


def prefill_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh):
    model = build_model(cfg)
    param_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    batch_spec = input_specs(cfg, shape)
    return model, (param_spec, batch_spec), (
        param_shardings(param_spec, mesh),
        batch_shardings(batch_spec, mesh),
    )


def make_decode_step(model: Model, mesh: Optional[Mesh], batch: int,
                     force_local: bool = False):
    n_kv = model.config.n_kv_heads
    rules = (
        decode_rules(mesh, batch) if mesh is not None else None
    )

    def decode_step(params, cache, token, pos):
        with use_rules(rules):
            return model.decode_step(
                params, cache, token, pos, force_local=force_local
            )

    return decode_step


def decode_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                 force_local: bool = False):
    model = build_model(cfg)
    param_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    B, S = shape.global_batch, shape.seq_len
    cache_spec = model.init_cache(B, S, spec_only=True,
                                  force_local=force_local)
    token_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    # decode weights: model-sharded only (no FSDP) when they fit —
    # otherwise every generated token re-all-gathers the weight shards
    # (§Perf). Models too big for model-only shards (dbrx: 263 GB bf16)
    # keep the FSDP layout.
    from repro.utils.pytree import tree_size_bytes

    # 4 GiB/chip resident-weight budget: conservative because XLA-CPU's
    # bf16->f32 dot conversions inflate measured temp; a TPU lowering
    # would admit llava-34b (4.3 GiB) resident too.
    model_n = mesh.shape.get("model", 1)
    resident_ok = tree_size_bytes(param_spec) / model_n < 4 * 2**30
    p_sh = param_shardings(param_spec, mesh, fsdp=not resident_ok)
    c_sh = cache_shardings(cache_spec, mesh, batch=B)
    t_sh = batch_shardings({"t": token_spec}, mesh)["t"]
    pos_sh = NamedSharding(mesh, P())
    # out_shardings for (new_cache, logits): the cache keeps its sharding so
    # donated input buffers alias in place (otherwise every decode step
    # copies the full KV cache — 32L x 1 GiB for minitron).
    from repro.launch.mesh import data_axis_names, n_data_shards

    dp = data_axis_names(mesh)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    dn = n_data_shards(mesh)
    model_n = mesh.shape.get("model", 1)
    logits_sh = NamedSharding(mesh, P(
        dp_spec if (B % dn == 0 and B >= dn) else None,
        "model" if cfg.vocab % model_n == 0 else None,
    ))
    out_sh = (c_sh, logits_sh)
    return model, (param_spec, cache_spec, token_spec, pos_spec), (
        p_sh, c_sh, t_sh, pos_sh
    ), out_sh


# ---------------------------------------------------------------------------
# aggregate — the paper's technique as a first-class lowered program
# ---------------------------------------------------------------------------


def make_aggregate_step(mesh: Mesh, n_clients: int):
    """FedAvg aggregation of n client updates of a model's parameters,
    sharded (clients x params) over (data-axes x model) — the paper's
    technique as a lowered program.

    shard_map + ``psum_scatter``: each device partial-sums its client
    shard, then the cross-client reduction SCATTERS the fused result over
    the data axes (half an all-reduce's ring traffic, and no chip ever
    materializes the full fused model). Leaves whose leading dim doesn't
    divide fall back to ``psum``."""
    from repro.launch.mesh import data_axis_names, n_data_shards

    dp = data_axis_names(mesh)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    dn = n_data_shards(mesh)
    data_axes = set(dp or ())
    # Few, giant clients (n < data shards — e.g. 8 x 245 GiB dbrx updates):
    # sharding the CLIENT dim is impossible/wasteful. Instead keep every
    # update FSDP-sharded over (data x model) on its PARAM dims and sum the
    # client dim locally — zero collectives, exact.
    param_sharded_mode = n_clients < dn
    if not param_sharded_mode:
        # pad the client axis to the shard multiple; padded rows carry
        # weight 0, so the weighted sum is exact
        n_clients = -(-n_clients // dn) * dn

    def _strip(sh):
        """Remove data axes from a param PartitionSpec (clients own them)."""
        stripped = []
        for entry in sh.spec:
            if entry is None:
                stripped.append(None)
            elif isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a not in data_axes)
                stripped.append(
                    kept if len(kept) > 1 else (kept[0] if kept else None)
                )
            else:
                stripped.append(None if entry in data_axes else entry)
        return stripped

    def specs(cfg: ModelConfig):
        model = build_model(cfg)
        p_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        stacked = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((n_clients,) + l.shape, l.dtype),
            p_spec,
        )
        base_sh = param_shardings(p_spec, mesh)

        if param_sharded_mode:
            # clients local, params FSDP-sharded; plain jit (no shard_map)
            in_sh = (
                jax.tree_util.tree_map(
                    lambda sh: NamedSharding(mesh, P(None, *sh.spec)),
                    base_sh,
                    is_leaf=lambda x: isinstance(x, NamedSharding),
                ),
                NamedSharding(mesh, P()),
            )

            def step(u_tree, w):
                wf = w.astype(jnp.float32)
                tot = jnp.sum(wf) + 1e-6

                def leaf_fuse(u):
                    uf = u.astype(jnp.float32)
                    wb = wf.reshape((-1,) + (1,) * (uf.ndim - 1))
                    return (jnp.sum(uf * wb, axis=0) / tot).astype(u.dtype)

                return jax.tree_util.tree_map(leaf_fuse, u_tree)

            return step, (
                stacked, jax.ShapeDtypeStruct((n_clients,), jnp.float32)
            ), in_sh, base_sh
        stripped = jax.tree_util.tree_map(
            lambda sh, leaf: (
                _strip(sh) + [None] * (len(leaf.shape) - len(sh.spec))
            ),
            base_sh, p_spec,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )

        def in_spec(st):
            return P(dp_spec, *st)

        def scatter_dim(leaf_spec, st):
            """First unsharded, dn-divisible param dim (or -1: psum)."""
            for i, size in enumerate(leaf_spec.shape):
                if st[i] is None and size % dn == 0 and size >= dn:
                    return i
            return -1

        def out_spec(leaf_spec, st):
            d = scatter_dim(leaf_spec, st)
            if d < 0:
                return P(*st)
            entries = list(st)
            entries[d] = dp_spec
            return P(*entries)

        in_specs = (
            jax.tree_util.tree_map(
                in_spec, stripped, is_leaf=lambda x: isinstance(x, list)
            ),
            P(dp_spec),
        )
        out_specs = jax.tree_util.tree_map(
            out_spec, p_spec, stripped,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        scatter_tree = jax.tree_util.tree_map(
            scatter_dim, p_spec, stripped,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )

        def local(u_tree, w):
            wf = w.astype(jnp.float32)
            tot = jax.lax.psum(jnp.sum(wf), dp) + 1e-6

            def leaf_fuse(u, sdim):
                uf = u.astype(jnp.float32)
                wb = wf.reshape((-1,) + (1,) * (uf.ndim - 1))
                partial = jnp.sum(uf * wb, axis=0)
                if sdim >= 0:
                    fused = jax.lax.psum_scatter(
                        partial, dp, scatter_dimension=sdim, tiled=True
                    )
                else:
                    fused = jax.lax.psum(partial, dp)
                return (fused / tot).astype(u.dtype)

            return jax.tree_util.tree_map(leaf_fuse, u_tree, scatter_tree)

        step = jax.shard_map(
            local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        in_sh = (
            jax.tree_util.tree_map(
                lambda st: NamedSharding(mesh, P(dp_spec, *st)), stripped,
                is_leaf=lambda x: isinstance(x, list),
            ),
            NamedSharding(mesh, P(dp_spec)),
        )
        out_sh = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), out_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        return step, (stacked, jax.ShapeDtypeStruct((n_clients,), jnp.float32)), in_sh, out_sh

    return specs
