"""Seeded weights, made on the device, leaf by leaf from a name.

Both sides take their weights from here: the run builds the program's tree
with :func:`build` inside one jitted call, and the reference makes the same
values again with :func:`leaf`, one layer at a time, long after the program's
copy is gone.  A leaf's values depend only on the seed, its path and (for a
leaf stacked over layers) the layer, never on how the tree is sharded:
JAX's partitionable threefry draws the same numbers on one chip or four.

Scales follow the usual initialisations: embeddings and the head 0.02, a
matrix 1/sqrt(rows), and norm scales and biases small, so that a wrong norm
or a dropped bias shows in the result.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

__all__ = ["root_key", "leaf", "scale", "build", "path_name"]


def root_key(seed: int):
    """A key from any whole number, beyond 32 bits too."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def scale(name: str, shape) -> float:
    last = name.rsplit("/", 1)[-1]
    if last in ("embed", "lm_head"):
        return 0.02
    if last in ("ln", "final_ln"):
        return 0.1
    if last.startswith("b"):
        return 0.02
    return 1.0 / math.sqrt(shape[-2])


def leaf(key, name: str, shape, layer=None, dtype=jnp.float32):
    """Values of leaf ``name`` (of layer ``layer`` where the tree stacks
    layers), as float32 unless ``dtype`` says otherwise."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return (jax.random.normal(k, tuple(shape), jnp.float32) * scale(name, shape)).astype(dtype)


def path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def build(key, shapes, stacked):
    """A tree shaped like ``shapes`` (a tree of ShapeDtypeStruct) with every
    leaf from :func:`leaf`.  Where ``stacked(name)`` holds, the leaf's first
    axis counts layers and each layer is drawn on its own."""

    def make(path, sds):
        name = path_name(path)
        if stacked(name):
            layers = sds.shape[0]
            return jax.vmap(lambda i: leaf(key, name, sds.shape[1:], i, sds.dtype))(
                jnp.arange(layers))
        return leaf(key, name, sds.shape, None, sds.dtype)

    return jax.tree_util.tree_map_with_path(make, shapes)
