"""Mesh construction and shard_map entry."""
from __future__ import annotations

import jax
from jax import lax

__all__ = ["make_mesh", "shard_map", "axis_size"]

axis_size = lax.axis_size


def make_mesh(shape, axis_names):
    """Mesh constructor pinned to Auto axis types (we use in_shardings/constraints)."""
    return jax.make_mesh(
        shape, axis_names, axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names)
    )


def shard_map(f, mesh, in_specs, out_specs, check_rep: bool = False, axis_names=None):
    """``jax.shard_map`` with replication checks off by default.

    ``axis_names``: when given, a partial-auto shard_map — only those mesh axes
    are manual; the rest stay under the automatic partitioner.
    """
    kw = {} if axis_names is None else {"axis_names": frozenset(axis_names)}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_rep, **kw
    )
