"""Slot-pool KV/SSM cache management for continuous batching.

A fixed pool of ``n_slots`` batch rows over ``lm.init_caches``: each admitted
request owns one row, its per-slot length masks every attention read, and
evicting a finished sequence is just re-seating the slot.  ``reset(slot)``
zeroes the row's cache/state — mandatory for the recurrent mamba SSM/conv
state (a stale recurrence would silently poison the next occupant; attention
rows are already excluded by the length masks, so zeroing them is hygiene).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.parallel.sharding import place

__all__ = ["SlotPool"]


def _zero_slot(caches, slot):
    """Zero one slot's rows across the whole cache tree.

    Prefix/suffix layer caches carry the slot on axis 0; scan (stacked unit)
    caches carry ``n_units`` first and the slot on axis 1.
    """

    def zero(axis):
        def f(leaf):
            idx = (slice(None),) * axis + (slot,)
            return leaf.at[idx].set(jnp.zeros((), leaf.dtype))

        return f

    out = {
        "prefix": [jax.tree_util.tree_map(zero(0), c) for c in caches["prefix"]],
        "suffix": [jax.tree_util.tree_map(zero(0), c) for c in caches["suffix"]],
    }
    if "scan" in caches:
        out["scan"] = [jax.tree_util.tree_map(zero(1), c) for c in caches["scan"]]
    return out


class SlotPool:
    """Device-resident cache pool; the engine threads ``caches`` through its
    jit'd step and writes the result back here."""

    def __init__(self, cfg, pc, n_slots: int, max_len: int, dtype=jnp.bfloat16):
        self.n_slots = n_slots
        self.max_len = max_len
        # placed with the model's cache shardings at construction, so reset
        # and the engine step see one sharding and trace once
        self.caches = place(lm.init_caches(cfg, pc, n_slots, max_len, dtype),
                            pc.mesh, lm.cache_specs(cfg, pc))
        # donation keeps the pool at one cache's footprint on real devices;
        # CPU has no donation support and would only log noise
        donate = () if jax.default_backend() == "cpu" else (0,)
        self._reset = jax.jit(_zero_slot, donate_argnums=donate)

    def reset(self, slot: int) -> None:
        """Evict whatever occupied ``slot``: zero its cache/state rows.

        Device-side only — enqueues one small jit'd update, no host sync.
        """
        self.caches = self._reset(self.caches, jnp.int32(slot))
