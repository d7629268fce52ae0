"""Fused GEMM + ReduceScatter Pallas kernel — paper Fig. 4, plan-driven.

Driven by the SAME :class:`~repro.core.plan.TilePlan` as the XLA backend: the
plan's reduce-scatter view (the time reversal of the order's source schedule —
for "ring" in the plan's default orientation exactly the paper's
``seg = (rank + stage + 1) % W``) is baked in
as int32 segment/destination tables, so ``CommSpec.order``, ``num_channels``
(column chunking, C independent flows), ``CompSpec.accum_dtype`` (the dtype
partials are *reduced* in), ``BlockChannel.quant`` (the wire dtype partials
*travel* in — a float wire is cast at each send edge and widened back before
the add) and the CompSpec (tm, tn, tk) compute tile behave identically on
both backends.

Stage ``s``, channel ``c`` at rank ``r``:
  1. ``consumer_tile_wait``   — wait for the partial pushed by the plan's
     stage-(s-1) peer (``wait_recv`` on the per-(stage, channel) semaphore);
  2. compute the GEMM tiles for segment ``seg_tbl[c, s, r]`` while the *next*
     incoming partial is still in flight;
  3. add the received partial (TopK-reduce-style epilogue fusion);
  4. ``tile_push_data`` + ``peer_tile_notify`` — push the new partial to
     ``dst_tbl[c, s, r]`` (for "ring": rank r-1, paper line 11).

After R stages each channel's accumulator holds the fully reduced home
segment and is stored to the local output columns (paper lines 22-23).

Race-freedom: receive buffers are slot-per-(stage, channel) (written exactly
once per pass — no credit counters needed); the outgoing partial is pushed
straight from the accumulator's channel columns, guarded by ``wait_send``
(release, §4.2) on a *per-channel* send semaphore before those columns are
overwritten next stage (a shared send semaphore makes the release credits of
concurrent channels interchangeable — a WAR race ``repro.analysis`` flags).

VMEM budget: the flowing accumulator [m_loc, N] and the world * C received
partials are resident in VMEM (about 16 MiB in f32 at smollm-360m's TP=4
down-projection, m_loc=512, N=960); the kernel asks the compiler for the
scoped VMEM its buffers take (``backend.vmem_limit_bytes``).
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

__all__ = ["gemm_rs_shard"]


def _gemm_rs_kernel(
    *refs,
    axis: str,
    world: int,
    nch: int,
    n_tiles: int,
    m_loc: int,
    n_sub: int,
    tm: int,
    bn: int,
    tk: int,
    accum,
    packed: bool,
    split: bool,
):
    if packed:
        # weight-only dequant-GEMM: int8/int4 codes + per-column scale/zero
        (x_ref, w_ref, scale_ref, zero_ref, seg_tbl, dst_tbl, o_ref,
         x_vmem, acc, prev, out_cast, copy_sem, send_sems, recv_sems,
         rbuf, *rest) = refs
    else:
        (x_ref, w_ref, seg_tbl, dst_tbl, o_ref,
         x_vmem, acc, prev, out_cast, copy_sem, send_sems, recv_sems,
         rbuf, *rest) = refs
        scale_ref = zero_ref = None
    # when the wire dtype differs from the accumulation dtype partials are
    # cast into a per-channel send staging buffer before each hop (the
    # accumulator itself stays in accum dtype)
    send_buf = rest[0] if split else None
    s = pl.program_id(0)
    c = pl.program_id(1)
    j = pl.program_id(2)
    my = lax.axis_index(axis)
    flat = (c * world + s) * world + my
    seg = seg_tbl[flat]  # segment this rank reduces at stage s
    dst = dst_tbl[flat]  # peer that reduces it at stage s+1
    # lane offsets of this channel's and this tile's columns: multiples of
    # 128, or a static 0 when one block spans the whole width
    c0 = pl.multiple_of(c * n_sub, 128) if nch > 1 else 0
    col = pl.multiple_of(c0 + j * bn, 128) if n_tiles > 1 or nch > 1 else 0
    jb = pl.multiple_of(j * bn, 128) if n_tiles > 1 else 0

    if world > 1:

        @pl.when((s == 0) & (c == 0) & (j == 0))
        def _enter():
            # no peer pushes into our receive buffers before we run this kernel
            primitives.rank_barrier(my, world)

    def _push_rdma(stage):
        # identical descriptor on sender & receiver (SPMD) — sender start()s,
        # receiver wait_recv()s, sender wait_send()s before the source
        # columns are overwritten.  Source: the channel's accumulator columns
        # (wire == accum), or the channel's rows of the wire-dtype staging
        # buffer (wire != accum).  The send semaphore is per-channel: with a
        # shared one the wait_send credits of concurrent channels are
        # interchangeable, so channel c's stage-(s-1) push could still be
        # reading its source when stage s overwrites it (analysis.protocol
        # flags this as overwritten_before_wait for num_channels >= 2).
        if split:
            src = send_buf.at[pl.ds(c * m_loc, m_loc), :]
        else:
            src = acc.at[:, pl.ds(c0, n_sub)]
        return primitives.make_tile_push(
            src_ref=src,
            dst_ref=rbuf.at[stage * nch + c],
            send_sem=send_sems.at[c],
            recv_sem=recv_sems.at[stage * nch + c],
            rank=dst,
        )

    # channels sharing a direction reduce the same segment at the same stage
    # (always for ring/all2all) — skip the HBM->VMEM refetch when the segment
    # x_vmem already holds (previous channel, same stage) is the one we need
    prev_flat = (jnp.maximum(c - 1, 0) * world + s) * world + my
    seg_is_stale = jnp.logical_or(c == 0, seg != seg_tbl[prev_flat])

    @pl.when(j == 0)
    def _stage_setup():
        @pl.when(seg_is_stale)
        def _fetch_seg():
            # shape mapping f_S: bring segment `seg` of x into VMEM
            cp = backend.make_async_copy(x_ref.at[pl.ds(seg * m_loc, m_loc), :], x_vmem, copy_sem)
            cp.start()
            cp.wait()

        @pl.when(s > 0)
        def _recv_prev():
            # consumer_tile_wait (acquire): stage s-1 partial for channel c
            _push_rdma(s - 1).wait_recv()
            cp2 = backend.make_async_copy(rbuf.at[(s - 1) * nch + c], prev, copy_sem)
            cp2.start()
            cp2.wait()
            # release: our stage s-1 push drained before acc cols are reused
            _push_rdma(s - 1).wait_send()

    # GEMM tile j for segment `seg` (+ fused reduction of the incoming
    # partial); a tuned (tm, tk) decomposes the [m_loc, k_loc] x [k_loc, bn]
    # contraction into explicit MXU blocks, the default keeps one dot
    w_val = w_ref[...]
    if packed:
        # dequant in VMEM right before the MXU: the [k_loc, bn] block arrives
        # as int8 codes (int4 codes in an int8 container), so HBM->VMEM moves
        # 1/2-1/4 the bytes; scales/zeros are per output column
        w_val = (w_val.astype(accum) - zero_ref[0, :][None, :]) * scale_ref[0, :][None, :]
    part = blocked_dot(x_vmem[...], w_val, (tm, bn, tk), accum=accum, unroll=True)

    @pl.when(s > 0)
    def _add_prev():
        acc[:, pl.ds(col, bn)] = part + prev[:, pl.ds(jb, bn)].astype(part.dtype)

    @pl.when(s == 0)
    def _no_prev():
        acc[:, pl.ds(col, bn)] = part

    @pl.when(j == n_tiles - 1)
    def _stage_finish():
        @pl.when(s < world - 1)
        def _push():
            if split:
                # wire-dtype cast at the send edge; safe to overwrite — the
                # stage-(s-1) push from these rows drained at this stage's
                # j == 0 wait_send
                send_buf[pl.ds(c * m_loc, m_loc), :] = (
                    acc[:, pl.ds(c0, n_sub)].astype(send_buf.dtype))
            _push_rdma(s).start()  # tile_push_data + peer_tile_notify

        @pl.when(s == world - 1)
        def _store():
            # paper lines 22-23: final stage stores the reduced home segment
            out_cast[...] = acc[:, pl.ds(c0, n_sub)].astype(out_cast.dtype)
            cp = backend.make_async_copy(out_cast, o_ref.at[:, pl.ds(c0, n_sub)], copy_sem)
            cp.start()
            cp.wait()


def gemm_rs_shard(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    channel: Optional[BlockChannel] = None,
    world_size: int,
    bn: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Per-shard fused GEMM+RS. x: [M, k_loc], w: [k_loc, N] -> [M/R, N].

    Call inside shard_map over ``channel.axis``; the schedule (order,
    channels), the accumulation dtype (``channel.comp.accum_dtype``) and the
    wire dtype partials travel in (``channel.quant`` — a float wire casts at
    each send edge, the default inherits the accumulation dtype), and the
    (tm, tn, tk) compute tile come from ``channel`` via the plan layer;
    ``bn`` overrides ``channel.comp.tile[1]``.  ``w`` may be a
    :class:`~repro.core.quant.PackedWeight` (weight-only int8/int4): the
    weight blocks stream HBM->VMEM as integer codes and are dequantized in
    VMEM right before the MXU.  Quantized *activation* wires (int8/fp8) are
    XLA-backend only — the scale side-channel per remote DMA is not plumbed
    here.  ``interpret=None`` lets the backend target decide (Mosaic on
    "tpu", the interpreter on "emulated").

    The lane block ``bn`` is a multiple of 128 dividing the channel width
    ``N / C``, with ``N`` padded to 128
    (:func:`~repro.core.comp_tiles.lane_block`); other (N, C) pairs raise.
    """
    channel = channel or BlockChannel(axis="model")
    if channel.quant.is_quantized:
        raise NotImplementedError(
            "gemm_rs_shard: quantized activation wires (QuantSpec.wire_dtype="
            f"{channel.quant.wire_dtype!r}) are not supported by the fused "
            "Pallas kernel; use backend='xla' (weight-only quantization via "
            "PackedWeight IS supported here)")
    axis = channel.axis
    m_glob, k_loc = x.shape
    packed = isinstance(w, PackedWeight)
    n_out = w.shape[1]
    assert m_glob % world_size == 0
    m_loc = m_glob // world_size
    # lane dims padded to 128 (Mosaic slices no other ref); the padded
    # contraction rows are zero, the padded output columns are dropped
    k_loc, n = round_up(k_loc), round_up(n_out)
    x, w = pad2(x, m_glob, k_loc), pad2(w, k_loc, n)

    nch = effective_channels(n, channel.num_channels, kind="matmul_rs")
    plan = build_plan("matmul_rs", channel, world_size, nch)
    n_sub = n // nch
    comp_tile = tuple(channel.comp.tile)
    bn = lane_block(n_sub, bn or comp_tile[1], what=f"gemm_rs N={n_out} with num_channels={nch}")
    n_tiles = n_sub // bn
    if comp_tile == DEFAULT_TILE:
        # sentinel: backend-chosen blocking — whole-segment rows/contraction
        tm, tk = m_loc, k_loc
    else:
        tm = largest_divisor(m_loc, comp_tile[0])
        tk = largest_divisor(k_loc, comp_tile[2])
    accum = jnp.dtype(plan.accum_dtype)
    wire = jnp.dtype(plan.flow_dtype)
    split = wire != accum
    seg_tbl = jnp.asarray(plan.rs_seg_tables(), jnp.int32).reshape(-1)
    dst_tbl = jnp.asarray(plan.rs_dst_tables(), jnp.int32).reshape(-1)

    kern = functools.partial(
        _gemm_rs_kernel,
        axis=axis,
        world=world_size,
        nch=nch,
        n_tiles=n_tiles,
        m_loc=m_loc,
        n_sub=n_sub,
        tm=tm,
        bn=bn,
        tk=tk,
        accum=accum,
        packed=packed,
        split=split,
    )
    in_specs = [
        pl.BlockSpec(memory_space=backend.HBM),
        pl.BlockSpec((k_loc, bn), lambda s, c, j: (0, c * (n_sub // bn) + j)),
    ]
    operands = [x]
    if packed:
        operands.append(w.q)
        # per-output-column scale/zero ride as (1, bn) blocks next to the
        # weight block they dequantize (zero points default to 0 — symmetric)
        zero = w.zero if w.zero is not None else jnp.zeros_like(w.scale)
        operands.extend([w.scale.reshape(1, n), zero.reshape(1, n)])
        in_specs.extend([
            pl.BlockSpec((1, bn), lambda s, c, j: (0, c * (n_sub // bn) + j)),
            pl.BlockSpec((1, bn), lambda s, c, j: (0, c * (n_sub // bn) + j)),
        ])
    else:
        operands.append(w)
    in_specs.extend([
        pl.BlockSpec(memory_space=backend.SMEM),  # segment schedule table
        pl.BlockSpec(memory_space=backend.SMEM),  # push-dst schedule table
    ])
    operands.extend([seg_tbl, dst_tbl])
    vmem = [
        ((m_loc, k_loc), x.dtype),  # x segment
        ((m_loc, n), accum),  # stage accumulator
        ((m_loc, n_sub), wire),  # received partial
        ((m_loc, n_sub), x.dtype),  # final cast
    ]
    rbuf = ((world_size * nch, m_loc, n_sub), wire)
    # per-channel wire-dtype send staging (rows c*m_loc:(c+1)*m_loc)
    staging = [((nch * m_loc, n_sub), wire)] if split else []
    scratch = [backend.vmem_scratch(*a) for a in vmem] + [
        backend.dma_semaphore(),  # local copies
        backend.dma_semaphore((nch,)),  # per-channel sends (release order)
        backend.dma_semaphore((world_size * nch,)),  # per-(stage,ch) recv
        backend.vmem_scratch(*rbuf),
    ] + [backend.vmem_scratch(*a) for a in staging]
    # the pipelined (k_loc, bn) weight block (+ its scale/zero rows) is double-buffered
    blocks = [((k_loc, bn), operands[1].dtype)] + [((1, bn), jnp.float32)] * (2 * packed)
    footprint = sum(backend.vmem_array_bytes(*a) for a in vmem + [rbuf] + staging) + 2 * sum(
        backend.vmem_array_bytes(*b) for b in blocks)
    out = backend.pallas_call(
        kern,
        name="gemm_rs",
        grid=(world_size, nch, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=backend.HBM),
        out_shape=jax.ShapeDtypeStruct((m_loc, n), x.dtype),
        scratch_shapes=scratch,
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        compiler_params_kw=dict(
            collective_id=0, vmem_limit_bytes=backend.vmem_limit_bytes(footprint)),
        interpret=interpret,
    )(*operands)
    return out[:, :n_out] if n_out != n else out
