"""Re-export of ``repro.backend``'s mesh helpers and ``jax.tree_util``.

Kept so existing imports (``from repro.compat import shard_map, make_mesh``)
keep working; new code should import ``repro.backend`` directly.
"""
from __future__ import annotations

import jax

from repro.backend import make_mesh, shard_map  # noqa: F401

__all__ = ["shard_map", "make_mesh", "tree_map", "tree_leaves",
           "tree_flatten", "tree_unflatten"]

tree_map = jax.tree_util.tree_map
tree_leaves = jax.tree_util.tree_leaves
tree_flatten = jax.tree_util.tree_flatten
tree_unflatten = jax.tree_util.tree_unflatten
