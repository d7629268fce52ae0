"""MXU-tiled matmul Pallas kernel (fp32 accumulation in VMEM scratch).

The consumer-side compute tile of TileLink programs: block shapes are the
CompSpec tile of the decoupled design space.  Grid is (M/bm, N/bn, K/bk) with
the K dimension innermost so the VMEM accumulator lives across K steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import backend
from repro.backend import pl

__all__ = ["matmul", "DEFAULT_TILE"]

DEFAULT_TILE = (128, 128, 128)  # (bm, bn, bk) — MXU-aligned


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype", "interpret"))
def matmul(x, w, *, tile=DEFAULT_TILE, out_dtype=None, interpret=False):
    """x: [M, K] @ w: [K, N] -> [M, N]; M/N/K must divide by the tile."""
    out_dtype = out_dtype or x.dtype
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = (min(tile[0], m), min(tile[1], n), min(tile[2], k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, w.shape, tile)
    n_k = k // bk

    return backend.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        name="matmul",
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[backend.vmem_scratch((bm, bn), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, w)
