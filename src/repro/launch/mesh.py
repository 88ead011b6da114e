"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests must see
the real single CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(axis_shapes, axis_names, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types: the engines place data
    with ``NamedSharding`` and ``shard_map``, not sharding-in-types
    (``jax.make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(AxisType.Auto,) * len(axis_names),
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def data_axis_names(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_data_shards(mesh: Mesh) -> int:
    n = 1
    for a in data_axis_names(mesh):
        n *= mesh.shape[a]
    return n
