"""Compute-tile utilities — the CompSpec (tm, tn, tk) half, realized.

``CompSpec.tile`` is the consumer-kernel MXU tile, chosen independently from
the communication tile (the core decoupling of the paper).  This module is
the one place its semantics live, shared by every executor:

  * :func:`largest_divisor` / :func:`resolve_tile` clamp a requested tile
    against the operand extents it must divide — the same largest-divisor
    rule ``mapping.effective_channels`` applies to the comm half, so a tuned
    tile degrades predictably instead of crashing on an awkward shape;
  * :func:`blocked_dot` computes a (possibly batched) GEMM in (tm, tn, tk)
    blocks accumulated in the accum dtype — the XLA-path compute callbacks
    (``core/overlap.py``) and the fused Pallas kernels
    (``kernels/ag_gemm.py``, ``gemm_rs.py``) all honor a non-default tile
    through it, so a tuner winner behaves identically on both backends;
  * :func:`tile_footprint_bytes` is the per-tile VMEM working set the tuner
    prunes its lattice against (``repro.tune.candidates``).

``DEFAULT_TILE`` (128, 128, 128) means "let the backend choose": the XLA
path hands the whole per-step GEMM to XLA's own tiler, the Pallas kernels
use their native blocking.  Only a non-default tile forces explicit blocks.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

from repro.core.quant import PackedWeight, dequantize_weight

__all__ = [
    "DEFAULT_TILE",
    "largest_divisor",
    "round_up",
    "pad2",
    "lane_block",
    "resolve_tile",
    "blocked_dot",
    "tile_footprint_bytes",
]

DEFAULT_TILE = (128, 128, 128)


def largest_divisor(extent: int, cap: int) -> int:
    """Largest divisor of ``extent`` that is <= ``cap`` (>= 1).

    Divisors are enumerated in factor pairs up to ``sqrt(extent)`` —
    O(sqrt(extent)) always — instead of decrementing from ``cap``, which is
    O(extent) when ``extent`` is prime and ``cap`` is large (a vocab-sized
    prime dim would spin for seconds per lattice probe).
    """
    extent = max(1, int(extent))
    cap = min(max(1, int(cap)), extent)
    best = 1
    d = 1
    while d * d <= extent:
        if extent % d == 0:
            if d <= cap and d > best:
                best = d
            pair = extent // d
            if pair <= cap and pair > best:
                best = pair
        d += 1
    return best


def round_up(n: int, multiple: int = 128) -> int:
    return -(-int(n) // multiple) * multiple


def pad2(a, rows: int, cols: int):
    """Zero-pad a 2-D operand (or a PackedWeight, whose padded columns
    dequantize to 0) to [rows, cols].

    Mosaic slices a VMEM or HBM ref only when its last dim is a multiple of
    128, so the fused kernels pad their operands' lane dims to 128 and drop
    the padded output columns.
    """
    r, c = a.shape
    if (r, c) == (rows, cols):
        return a
    if isinstance(a, PackedWeight):
        vec = (0, cols - c)
        return PackedWeight(
            pad2(a.q, rows, cols), jnp.pad(a.scale, vec),
            None if a.zero is None else jnp.pad(a.zero, vec), a.dtype)
    return jnp.pad(a, ((0, rows - r), (0, cols - c)))


def lane_block(extent: int, want: int, *, what: str) -> int:
    """Lane-dim (last-dim) block of a Pallas TPU BlockSpec over ``extent``.

    Mosaic slices a lane dim only in multiples of 128.  Returns the largest
    multiple of 128 that divides ``extent`` and is <= ``max(want, 128)``;
    raises a NotImplementedError naming ``what`` when ``extent`` is off the
    128 grid.
    """
    lane = 128
    if extent % lane:
        raise NotImplementedError(
            f"{what}: a lane block over {extent} columns must be a multiple of "
            "128 that divides them; none does"
        )
    cap = min(max(int(want), lane), extent)
    return max(b for b in range(lane, cap + 1, lane) if extent % b == 0)


def resolve_tile(tile: Tuple[int, int, int], m: int, n: int, k: int) -> Tuple[int, int, int]:
    """Clamp a requested (tm, tn, tk) to divisors of the GEMM dims (m, n, k)."""
    tm, tn, tk = tile
    return (largest_divisor(m, tm), largest_divisor(n, tn), largest_divisor(k, tk))


def blocked_dot(
    a: jnp.ndarray,
    b: jnp.ndarray,
    tile: Tuple[int, int, int],
    accum=jnp.float32,
    out_dtype: Optional[jnp.dtype] = None,
    unroll: bool = False,
) -> jnp.ndarray:
    """``a @ b`` computed in (tm, tn, tk) blocks, accumulated in ``accum``.

    ``a``: [..., m, k] (leading batch dims allowed), ``b``: [k, n] — or a
    :class:`~repro.core.quant.PackedWeight` of the same logical shape
    (weight-only int8/int4): its codes are dequantized with their
    per-output-channel scales/zero-points INSIDE the decomposition, per
    (tk, tn) block on the ``unroll=True`` path — in VMEM right before the
    MXU in the Pallas kernel bodies — and as one fused elementwise producer
    on the XLA paths (XLA fuses it into the dot's operand read).  The tile
    is clamped through :func:`resolve_tile` first; a tile covering the whole
    problem takes the single-dot fast path (bit-identical to the untiled
    contraction).

    Two lowerings of the same block decomposition:

      * ``unroll=False`` (default, the XLA executor path): operands reshape
        to explicit [m/tm, tm, ...] block form and contract in ONE
        ``dot_general`` — O(1) emitted ops regardless of block count, so a
        tuned tile on a large shape cannot blow up trace/compile time;
      * ``unroll=True`` (the Pallas kernel bodies): explicit per-block 2-D
        dots accumulated in registers — the Mosaic-friendly form (4-D
        multi-contraction dots do not lower there), where the block count
        is already bounded by the kernel's per-chunk operand sizes.
    """
    m, k = a.shape[-2], a.shape[-1]
    packed = isinstance(b, PackedWeight)
    n = b.shape[-1]
    accum = jnp.dtype(accum)
    tm, tn, tk = resolve_tile(tile, m, n, k)

    def dot(x, y):
        dims = (((x.ndim - 1,), (0,)), ((), ()))
        return lax.dot_general(x, y, dims, preferred_element_type=accum)

    if (tm, tn, tk) == (m, n, k):
        bv = dequantize_weight(b.q, b.scale, b.zero, accum) if packed else b
        out = dot(a, bv)
        return out.astype(out_dtype) if out_dtype is not None else out

    if not unroll:
        # whole-weight dequant here is the same fused elementwise producer
        # XLA builds for the per-block form — only the Pallas path below
        # needs the dequant spelled per block (VMEM residency)
        bv = dequantize_weight(b.q, b.scale, b.zero, accum) if packed else b
        lead = a.shape[:-2]
        a4 = a.reshape(lead + (m // tm, tm, k // tk, tk))
        b4 = bv.reshape(k // tk, tk, n // tn, tn)
        nd = a4.ndim
        # contract (k-block, tk) jointly: the blocked layout stays explicit,
        # the emitted program stays a single op
        dims = (((nd - 2, nd - 1), (0, 1)), ((), ()))
        out = lax.dot_general(a4, b4, dims, preferred_element_type=accum)
        out = out.reshape(lead + (m, n))  # [..., m/tm, tm, n/tn, tn] -> [..., m, n]
        return out.astype(out_dtype) if out_dtype is not None else out

    def b_block(ni, ki):
        """One (tk, tn) weight block, dequantized at the point of use."""
        ns = slice(ni * tn, (ni + 1) * tn)
        ks = slice(ki * tk, (ki + 1) * tk)
        if not packed:
            return b[ks, ns]
        zero = None if b.zero is None else b.zero[ns]
        return dequantize_weight(b.q[ks, ns], b.scale[ns], zero, accum)

    rows = []
    for mi in range(m // tm):
        a_mi = a[..., mi * tm : (mi + 1) * tm, :]
        cols = []
        for ni in range(n // tn):
            blk = dot(a_mi[..., 0:tk], b_block(ni, 0))
            for ki in range(1, k // tk):
                blk = blk + dot(a_mi[..., ki * tk : (ki + 1) * tk], b_block(ni, ki))
            cols.append(blk)
        rows.append(cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=-1))
    out = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=-2)
    return out.astype(out_dtype) if out_dtype is not None else out


def tile_footprint_bytes(tile: Tuple[int, int, int], in_bytes: int, accum_bytes: int) -> int:
    """Per-tile VMEM working set: A and B operand tiles + the accumulator."""
    tm, tn, tk = tile
    return (tm * tk + tk * tn) * in_bytes + tm * tn * accum_bytes
