"""Fused AllGather + GEMM Pallas kernel (paper §5, AG+GEMM; push mode).

One kernel per device (launched under shard_map over the TP axis) both
*communicates* and *computes*, driven by the SAME :class:`~repro.core.plan.
TilePlan` the XLA backend executes — the plan's per-(channel, step, rank)
source and destination tables are baked into the kernel as int32 schedule
tables, so ``CommSpec.order`` (ring / bidir_ring / all2all) and
``num_channels`` behave identically on both backends:

  * step ``s``, channel ``c``: the sub-chunk this rank holds (origin
    ``src_tbl[c, s, my]``) is forwarded to ``dst_tbl[c, s, my]`` with
    ``tile_push_data`` (``pltpu.make_async_remote_copy`` on the ICI DMA
    engine) while the MXU computes GEMM tiles on it — communication and
    computation tiles are *decoupled*: the comm tile is the [m_sub, K]
    channel sub-chunk (f_C), the compute tile is the CompSpec (tm, bn, tk)
    blocking of it (``core/comp_tiles.blocked_dot``; the default tile keeps
    the whole-chunk dot), iterated in the inner grid dimension;
  * ``consumer_tile_wait`` is the ``wait_recv`` on the per-(step, channel)
    DMA semaphore — acquire semantics; loads of the gathered chunk are
    emitted only after it (paper §4.2's strict-dependency rule, enforced by
    construction).

Slot-per-(origin, channel) gather buffer makes the schedule race-free without
credit counters: every tile visits every rank exactly once (the plan's source
schedules are per-step and per-rank permutations), so each slot is written
exactly once per pass.

Validated on CPU via the backend's emulated target (the interpreter simulates
the inter-device DMAs + semaphores); on real TPU the same code lowers to
Mosaic with ICI RDMA.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro import backend
from repro.backend import pl
from repro.core import primitives
from repro.core.channels import BlockChannel
from repro.core.comp_tiles import (
    DEFAULT_TILE, blocked_dot, lane_block, largest_divisor, pad2, round_up)
from repro.core.mapping import effective_channels
from repro.core.plan import build_plan
from repro.core.quant import PackedWeight

__all__ = ["ag_gemm_shard"]


def _ag_gemm_kernel(
    *refs,
    axis: str,
    world: int,
    nch: int,
    n_tiles: int,
    m_loc: int,
    m_sub: int,
    tm: int,
    bn: int,
    tk: int,
    accum,
    packed: bool,
):
    if packed:
        # weight-only dequant-GEMM: int8/int4 codes + per-column scale/zero
        (x_ref, w_ref, scale_ref, zero_ref, src_tbl, dst_tbl, o_ref,
         buf, x_vmem, acc, out_tile, copy_sem, send_sem, recv_sems,
         out_sem) = refs
    else:
        (x_ref, w_ref, src_tbl, dst_tbl, o_ref,
         buf, x_vmem, acc, out_tile, copy_sem, send_sem, recv_sems,
         out_sem) = refs
        scale_ref = zero_ref = None
    s = pl.program_id(0)
    c = pl.program_id(1)
    j = pl.program_id(2)
    my = lax.axis_index(axis)
    flat = (c * world + s) * world + my
    src = src_tbl[flat]  # origin (== gather slot) consumed this step
    dst = dst_tbl[flat]  # peer the held tile is forwarded to
    slot = src * nch + c

    if world > 1:

        @pl.when((s == 0) & (c == 0) & (j == 0))
        def _enter():
            # no peer pushes into our gather buffer before we run this kernel
            primitives.rank_barrier(my, world)

    @pl.when(jnp.logical_and(s == 0, j == 0))
    def _local_seed():
        # stage channel c of the own shard into its gather slot (producer tile)
        cp = backend.make_async_copy(
            x_ref.at[pl.ds(c * m_sub, m_sub), :], buf.at[my * nch + c], copy_sem
        )
        cp.start()
        cp.wait()

    def _fwd_rdma():
        # forward from the VMEM staging copy (x_vmem) to the peer's gather
        # slot — src and dst must not alias for the DMA engine
        return primitives.make_tile_push(
            src_ref=x_vmem,
            dst_ref=buf.at[slot],
            send_sem=send_sem,
            recv_sem=recv_sems.at[s * nch + c],
            rank=dst,
        )

    @pl.when(j == 0)
    def _comm():
        # consumer_tile_wait + bring the tile to VMEM for the MXU
        cp = backend.make_async_copy(buf.at[slot], x_vmem, copy_sem)
        cp.start()
        cp.wait()

        # tile_push_data: forward the held tile along the plan's schedule
        # (overlaps with this step's GEMM tiles below)
        @pl.when(s < world - 1)
        def _():
            _fwd_rdma().start()

    # compute tile j of the consumer GEMM (CompSpec tile, accum dtype);
    # a tuned (tm, tk) decomposes the [m_sub, k] x [k, bn] contraction into
    # explicit MXU blocks, the default keeps the whole-chunk dot
    w_val = w_ref[...]
    if packed:
        # dequant in VMEM right before the MXU: the [k, bn] block arrives as
        # int8 codes (int4 codes in an int8 container), so HBM->VMEM moves
        # 1/2-1/4 the bytes; scales/zeros are per output column
        w_val = (w_val.astype(accum) - zero_ref[0, :][None, :]) * scale_ref[0, :][None, :]
    acc[...] = blocked_dot(x_vmem[...], w_val, (tm, bn, tk), accum=accum, unroll=True)
    out_tile[...] = acc[...].astype(out_tile.dtype)
    oc = backend.make_async_copy(
        out_tile,
        o_ref.at[pl.ds(src * m_loc + c * m_sub, m_sub), pl.ds(j * bn, bn)],
        out_sem,
    )
    oc.start()
    oc.wait()

    @pl.when(jnp.logical_and(j == n_tiles - 1, s < world - 1))
    def _finish_comm():
        # wait_send: x_vmem is drained (safe to reuse next channel/step);
        # wait_recv: the tile for step s+1 arrived
        _fwd_rdma().wait()


def ag_gemm_shard(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    channel: Optional[BlockChannel] = None,
    world_size: int,
    bn: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Per-shard fused AG+GEMM. x: [m_loc, K], w: [K, n_loc] -> [R*m_loc, n_loc].

    Call inside shard_map over ``channel.axis``.  The schedule (order,
    channels), the accumulation dtype (``channel.comp.accum_dtype`` — the
    reduction dtype, independent of what travels), and the (tm, tn, tk)
    compute tile come from ``channel`` via the plan layer; ``bn`` overrides
    ``channel.comp.tile[1]``.  ``w`` may be a
    :class:`~repro.core.quant.PackedWeight` (weight-only int8/int4): the
    weight blocks stream HBM->VMEM as integer codes and are dequantized in
    VMEM right before the MXU.  Quantized *activation* wires
    (``channel.quant.wire_dtype`` int8/fp8) are XLA-backend only — the scale
    side-channel per remote DMA is not plumbed here; this raises rather than
    silently sending unscaled codes.  ``interpret=None`` lets the backend
    target decide (Mosaic on "tpu", the interpreter on "emulated").

    The lane block ``bn`` is a multiple of 128 dividing ``n_loc`` padded to
    128 (:func:`~repro.core.comp_tiles.lane_block`).  The whole
    gathered operand ``[world * m_loc, K]`` sits in VMEM, which bounds the
    widths this kernel takes (see ROADMAP).
    """
    channel = channel or BlockChannel(axis="model")
    if channel.quant.is_quantized:
        raise NotImplementedError(
            "ag_gemm_shard: quantized activation wires (QuantSpec.wire_dtype="
            f"{channel.quant.wire_dtype!r}) are not supported by the fused "
            "Pallas kernel; use backend='xla' (weight-only quantization via "
            "PackedWeight IS supported here)")
    axis = channel.axis
    m_loc, k = x.shape
    packed = isinstance(w, PackedWeight)
    n_out = w.shape[1]
    # lane dims padded to 128 (Mosaic slices no other ref); the padded
    # contraction rows are zero, the padded output columns are dropped
    k, n_loc = round_up(k), round_up(n_out)
    x, w = pad2(x, m_loc, k), pad2(w, k, n_loc)
    comp_tile = tuple(channel.comp.tile)
    bn = lane_block(n_loc, bn or comp_tile[1], what=f"ag_gemm n_loc={n_out}")
    n_tiles = n_loc // bn

    nch = effective_channels(m_loc, channel.num_channels, kind="ag_matmul")
    plan = build_plan("ag_matmul", channel, world_size, nch)
    m_sub = m_loc // nch
    if comp_tile == DEFAULT_TILE:
        # sentinel: backend-chosen blocking — whole-chunk rows/contraction
        tm, tk = m_sub, k
    else:
        tm = largest_divisor(m_sub, comp_tile[0])
        tk = largest_divisor(k, comp_tile[2])
    accum = jnp.dtype(plan.accum_dtype)
    src_tbl = jnp.asarray(plan.src_tables(), jnp.int32).reshape(-1)
    dst_tbl = jnp.asarray(plan.flow_dst_tables(), jnp.int32).reshape(-1)

    kern = functools.partial(
        _ag_gemm_kernel,
        axis=axis,
        world=world_size,
        nch=nch,
        n_tiles=n_tiles,
        m_loc=m_loc,
        m_sub=m_sub,
        tm=tm,
        bn=bn,
        tk=tk,
        accum=accum,
        packed=packed,
    )
    in_specs = [
        pl.BlockSpec(memory_space=backend.HBM),
        pl.BlockSpec((k, bn), lambda s, c, j: (0, j)),
    ]
    operands = [x]
    if packed:
        operands.append(w.q)
        # per-output-column scale/zero ride as (1, bn) blocks next to the
        # weight block they dequantize (zero points default to 0 — symmetric)
        zero = w.zero if w.zero is not None else jnp.zeros_like(w.scale)
        operands.extend([w.scale.reshape(1, n_loc), zero.reshape(1, n_loc)])
        in_specs.extend([
            pl.BlockSpec((1, bn), lambda s, c, j: (0, j)),
            pl.BlockSpec((1, bn), lambda s, c, j: (0, j)),
        ])
    else:
        operands.append(w)
    in_specs.extend([
        pl.BlockSpec(memory_space=backend.SMEM),  # src schedule table
        pl.BlockSpec(memory_space=backend.SMEM),  # dst schedule table
    ])
    operands.extend([src_tbl, dst_tbl])
    vmem = [
        ((world_size * nch, m_sub, k), x.dtype),  # gather
        ((m_sub, k), x.dtype),  # current tile
        ((m_sub, bn), accum),  # accumulator
        ((m_sub, bn), x.dtype),  # cast staging tile
    ]
    # the pipelined (k, bn) weight block (+ its scale/zero rows) is double-buffered
    blocks = [((k, bn), operands[1].dtype)] + [((1, bn), jnp.float32)] * (2 * packed)
    footprint = sum(backend.vmem_array_bytes(*a) for a in vmem) + 2 * sum(
        backend.vmem_array_bytes(*b) for b in blocks)
    out = backend.pallas_call(
        kern,
        name="ag_gemm",
        grid=(world_size, nch, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=backend.HBM),
        out_shape=jax.ShapeDtypeStruct((world_size * m_loc, n_loc), x.dtype),
        scratch_shapes=[backend.vmem_scratch(*a) for a in vmem] + [
            backend.dma_semaphore(),  # local copies
            backend.dma_semaphore(),  # sends
            backend.dma_semaphore((world_size * nch,)),  # per-(step, ch) recv
            backend.dma_semaphore(),  # out stores
        ],
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        compiler_params_kw=dict(
            collective_id=0, vmem_limit_bytes=backend.vmem_limit_bytes(footprint)),
        interpret=interpret,
    )(*operands)
    return out[:, :n_out] if n_out != n_loc else out
