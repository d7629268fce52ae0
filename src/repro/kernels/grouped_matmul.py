"""Grouped (MoE) matmul Pallas kernel driven by *dynamic mapping tables*.

The paper's dynamic tile-centric mapping (§4.1): tile -> expert assignment is a
runtime lookup table (f_R), filled by the router; only the *access pattern* is
compiled.  Here the table is a scalar-prefetch operand — Mosaic reads
``tile_expert[tile_id]`` inside the BlockSpec index_map to choose which
expert's weight block to DMA into VMEM.  This is the TPU-native equivalent of
the paper's table-driven Triton codegen (Fig. 5).

x rows are expert-sorted and tile-aligned (build_moe_dynamic_mapping pads each
group to the row-tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import backend
from repro.backend import pl
from repro.core.comp_tiles import largest_divisor

__all__ = ["grouped_matmul"]


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype", "interpret"))
def grouped_matmul(x, w, tile_expert, *, tile=(128, 128, 128), out_dtype=None, interpret=False):
    """x: [M, K] (expert-sorted), w: [E, K, N], tile_expert: [M // bm] i32.

    Returns [M, N] with rows of tile t multiplied by w[tile_expert[t]].

    ``tile`` accepts any tuner-resolved (tm, tn, tk): each dim clamps to the
    largest divisor of its extent (the shared CompSpec degrade rule) instead
    of refusing non-dividing requests — note the row tile must still match
    the ``tile_expert`` table the mapping was built with.
    """
    out_dtype = out_dtype or x.dtype
    m, k = x.shape
    _, k2, n = w.shape
    assert k == k2
    bm = largest_divisor(m, min(int(tile[0]), m))
    bn = largest_divisor(n, min(int(tile[1]), n))
    bk = largest_divisor(k, min(int(tile[2]), k))
    assert tile_expert.shape == (m // bm,), (tile_expert.shape, m, bm)
    n_k = k // bk

    grid_spec = backend.prefetch_grid_spec(
        num_scalar_prefetch=1,
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, expert: (i, kk)),
            # dynamic mapping f_R: the runtime table chooses the weight block
            pl.BlockSpec((1, bk, bn), lambda i, j, kk, expert: (expert[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, expert: (i, j)),
        scratch_shapes=[backend.vmem_scratch((bm, bn), jnp.float32)],
    )

    def _kernel(expert_ref, x_ref, w_ref, o_ref, acc_ref):
        del expert_ref  # consumed by the index_maps above

        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32)

        @pl.when(pl.program_id(2) == n_k - 1)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return backend.pallas_call(
        _kernel,
        name="grouped_matmul",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(tile_expert, x, w)
