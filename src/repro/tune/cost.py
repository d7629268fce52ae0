"""Analytic cost model — the ranking fallback when wall time is no signal.

CPU wall time on the emulated target does not predict TPU behavior (ROADMAP),
and resolution can also happen *inside* a trace, where timing is impossible.
This model ranks candidates from first principles in the spirit of
``launch/roofline.py``: per schedule step, bytes-on-wire over link bandwidth
vs. per-tile FLOPs over peak, composed into a pipelined makespan:

    t_step  = max(t_comm, t_comp)            (overlap: the slower engine gates)
    total   = (steps - 1) * t_step           (steady state)
            + (t_comm + t_comp) / C          (pipeline fill/drain: finer
                                              channels expose less head/tail)
            + alpha * C * steps              (per-transfer launch latency —
                                              what keeps C from growing forever)

Order effects: a bidirectional ring with >= 2 channels splits traffic across
both ICI link directions (halving per-link bytes); all2all pays the mean ring
distance per payload on a physical ring/torus — computed from the actual
``schedules.all2all_peer`` tables (``_order_hops``), never a closed-form
guess, so cost and schedule agree for non-power-of-2 worlds too.  Dtype on
the wire: with no tuned wire (``Candidate.flow is None``) the accum dtype
scales wire bytes only for flows whose *partials* travel (rs / ag_rs) — for
pure AG flows the input tiles travel in their own dtype, so the model is
dtype-neutral there and the enumeration order (float32 first) breaks the tie
deterministically.  A tuned wire dtype (``Candidate.flow``, the QuantSpec
axis) reprices EVERY travelling payload at its itemsize — AG tiles included
— plus a small per-payload scale-table overhead for the quantized wires;
that is the term that lets an int8 flow win comm-bound shapes.

Compute-tile terms (the CompSpec half): for the GEMM kinds ``t_comp`` is
itself a per-tile roofline over the realized (tm, tn, tk) blocking —

    t_comp = max(FLOPs / (peak * mxu_eff), bytes_touched / hbm_bw)
           + beta * n_tiles

where ``mxu_eff`` penalizes tiles narrower than the 128-wide systolic array,
``bytes_touched`` counts the A/B operand tiles streamed from VMEM/HBM per
block plus one accumulator write per (tm, tn) block (bigger tiles amortize
operand re-reads), and ``beta`` is the fixed per-tile issue cost (grid
iteration + copy descriptors) that keeps tiles from shrinking forever.  The
VMEM budget bounds them from above (pruned in ``tune/candidates``).

The attention consumer prices (tm, tk) as (block_q, block_kv): a per-tile
softmax+MXU roofline — the QK^T score tile's MXU utilization, a VPU term
for the fp32 online-softmax work, and a score-spill term (a whole-chunk
score matrix that cannot stay VMEM-resident pays an fp32 HBM round-trip —
exactly what a flash-style tile removes).  The MoE consumer prices the
per-expert grouped GEMMs with a tile-occupancy term: expert groups are
capacity-sized, so the last row tile of each expert pads to tm and wastes
MXU cycles.  All compute terms are accum-dtype-free — the wire dtype only
prices the wire — so AG flows keep the deterministic f32 tie-break.

``alpha`` and ``beta`` are the calibratable constants of the classic
alpha-beta model: defaults below, env overrides ``REPRO_TUNE_ALPHA`` /
``REPRO_TUNE_BETA`` (seconds) for calibration against a real TPU.  Hardware
constants come from ``repro.backend.chip()`` (the chip the program runs
on; TPU v5e on the emulated target) and the ``repro.backend`` MXU probe —
the model ranks relative candidates, so absolute calibration is not
critical.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Tuple

import jax.numpy as jnp

from repro import backend
from repro.core import schedules
from repro.core.comp_tiles import DEFAULT_TILE, largest_divisor, resolve_tile, tile_footprint_bytes
from repro.tune.candidates import (
    Candidate,
    GEMM_TILE_KINDS,
    _tile_dims,
    a2a_sigs,
    chunk_extent,
    seq_sigs,
)

__all__ = [
    "ALPHA_S",
    "BETA_TILE_S",
    "step_terms",
    "realized_tile",
    "comp_step_time",
    "predict_cost",
    "seam_saving",
    "predict_seq_cost",
    "a2a_saving",
    "predict_a2a_cost",
]

# per-transfer launch/synchronization latency (seconds); the alpha of a
# classic alpha-beta model.  ~1us per DMA descriptor + semaphore round.
ALPHA_S = float(os.environ.get("REPRO_TUNE_ALPHA", 1e-6))

# fixed per-compute-tile issue cost (seconds): one grid iteration's control
# flow + operand copy descriptors.  The beta of the compute half.
BETA_TILE_S = float(os.environ.get("REPRO_TUNE_BETA", 2e-7))

# bytes per element flowing tiles travel in (activations; bf16 on TPU)
_TILE_BYTES = 2

# online-softmax statistics and score tiles stay fp32 regardless of the flow
# dtype (core/overlap.ring_attention) — NOT the candidate's accum dtype, so
# the compute term stays accum-dtype-free and AG flows keep the f32 tie-break
_SCORE_BYTES = 4

# VPU elementwise ops per attention score (max, sub, exp, the running l/o
# rescale) and the VPU's throughput relative to the MXU peak — the softmax
# half of the attention roofline
_SOFTMAX_OPS = 8.0
_VPU_FRACTION = 1.0 / 16.0


# bytes per (token, slot) routing entry riding a dispatch tile: one int32
# expert id plus one float32 gate weight (the paper's f_R/f_S travel with data)
_ROUTE_BYTES = 8


# per-payload overhead of a quantized wire: one f32 scale per tile plus the
# descriptor bookkeeping of the side-channel table ride-along
_SCALE_OVERHEAD_BYTES = 64


def _flow_bytes(accum_dtype: str) -> int:
    return jnp.dtype(accum_dtype).itemsize


@functools.lru_cache(maxsize=None)
def _order_hops(order: str, world: int) -> float:
    """Mean ring-distance per payload of one schedule step for ``order``.

    Derived from the actual peer tables (``schedules.all2all_peer``) rather
    than a closed form, so the cost model and the baked schedule cannot
    disagree — in particular for non-power-of-2 worlds, where the all2all
    order falls back to rotation peers instead of XOR pairing.  Ring orders
    always step to a physical neighbor (one hop).
    """
    if order != "all2all" or world <= 1:
        return 1.0
    total = 0
    for s in range(1, world):
        for r in range(world):
            p = schedules.all2all_peer(r, s, world)
            total += min((p - r) % world, (r - p) % world)
    return max(1.0, total / float((world - 1) * world))


def _moe_rows(sig: Tuple[int, ...], world: int) -> float:
    """Effective grouped-GEMM token rows per step for a MoE signature.

    The base count is ``m_loc * top_k`` assignment rows.  The optional MoE
    signature axes refine it: ``sig[5]`` is the hottest-expert imbalance in
    quarter-units (4 == balanced; a hot expert gates the grouped GEMM), and
    ``sig[6]`` is the per-expert capacity row count (dropping bounds the
    work from above, so an aggressively low capacity factor models faster).
    """
    m_loc, _d_model, top_k, e_loc, _d_exp = sig[:5]
    rows = float(m_loc * max(1, top_k))
    if len(sig) > 5:
        rows *= max(1.0, sig[5] / 4.0)
    if len(sig) > 6:
        rows = min(rows, float(max(1, e_loc * world) * sig[6]))
    return rows


def step_terms(
    kind: str, sig: Tuple[int, ...], world: int, accum_dtype: str,
    wire_dtype: str = None,
) -> Tuple[float, float]:
    """(wire_bytes, flops) per schedule step per rank for one candidate.

    Bytes counts every flow the executor permutes each step (tiles and/or
    the travelling reduction); flops counts the tile compute consumed while
    those transfers are in flight (see core/overlap.run_plan).
    ``wire_dtype=None`` keeps the legacy pricing (tiles at the activation
    itemsize, travelling reductions at the accum itemsize); a tuned wire
    dtype reprices everything on the wire at its own itemsize plus the
    quantized-wire scale overhead.
    """
    if wire_dtype is None:
        fb = _flow_bytes(accum_dtype)
        tb, extra = _TILE_BYTES, 0.0
    else:
        from repro.core.quant import wire_itemsize

        fb = tb = wire_itemsize(wire_dtype)
        extra = float(_SCALE_OVERHEAD_BYTES) if wire_dtype not in (
            "float32", "bfloat16", "float16") else 0.0
    if kind == "ag_matmul":
        lead, m_loc, k, n_loc = sig
        lead = abs(lead)  # decode signatures carry a negated lead marker
        wire = lead * m_loc * k * tb + extra
        flops = 2.0 * lead * m_loc * k * n_loc
    elif kind == "matmul_rs":
        lead, m_glob, k_loc, n = sig
        lead = abs(lead)
        m_loc = max(1, m_glob // world)
        wire = lead * m_loc * n * fb + extra  # the accumulator is the flow
        flops = 2.0 * lead * m_loc * k_loc * n
    elif kind == "ag_attention":
        b, h, hkv, s_loc, d = sig
        wire = 2.0 * b * hkv * s_loc * d * tb + extra  # K and V tiles
        flops = 4.0 * b * h * s_loc * s_loc * d  # QK^T + PV
    elif kind == "ag_moe":
        m_loc, d_model, _top_k, _e_loc, d_exp = sig[:5]
        # double ring: token tiles flow forward AND the combined reduction
        # rides the same permutes (in the wire dtype)
        wire = m_loc * d_model * (tb + fb) + extra
        flops = 6.0 * _moe_rows(sig, world) * d_model * d_exp
    elif kind == "a2a_dispatch":
        m_loc, d_model, top_k, _e_loc, d_exp = sig[:5]
        # pairwise exchange of original token tiles plus the routing tables
        # (expert ids + gate weights) that travel with them
        wire = m_loc * d_model * tb + m_loc * max(1, top_k) * _ROUTE_BYTES
        # the expert FFN on landed tiles runs while the next exchange flies
        flops = 6.0 * _moe_rows(sig, world) * d_model * d_exp
    elif kind == "combine_rs":
        m_loc, d_model = sig[0], sig[1]
        # weighted partials return straight home in the wire dtype; the only
        # compute on this half is the per-token accumulate
        wire = m_loc * d_model * fb
        flops = 2.0 * m_loc * d_model
    else:
        raise ValueError(f"no cost model for kind {kind!r}")
    return float(wire), float(flops)


def realized_tile(
    kind: str, sig: Tuple[int, ...], world: int, cand: Candidate
) -> Tuple[int, int, int]:
    """The blocking a candidate's compute tile actually executes as.

    The DEFAULT_TILE sentinel realizes as what the consumers run when
    untuned — for the GEMM kinds whole-chunk rows and contraction with
    128-wide output columns; for attention the whole-chunk online-softmax
    update; for MoE the whole per-expert grouped GEMM — NOT as a literal
    128^3 decomposition, so the default is never charged per-tile costs its
    execution does not incur (a tuned tile must beat the real thing).
    Non-default tiles clamp like everywhere else.
    """
    m, n, k = _tile_dims(kind, tuple(sig), world, max(1, cand.num_channels))
    if tuple(cand.comp_tile) == DEFAULT_TILE:
        if kind in GEMM_TILE_KINDS:
            return m, largest_divisor(n, 128), k
        return m, n, k  # native: one whole-chunk consumer block
    return resolve_tile(tuple(cand.comp_tile), m, n, k)


def _spill_bytes(tm: int, tn: int, tk: int, acc_bytes: int) -> float:
    """Extra HBM round-trip a blocking pays when it cannot stay VMEM-resident.

    A blocking whose working set fits the probed budget keeps its
    accumulator (GEMM) or score tile (attention) on-chip; one that does not
    spills it to HBM — write + read-back.  This is the term a tuned
    flash-style tile exists to remove, and it is what lets a non-default
    attention/MoE tile beat the whole-chunk native blocking on shapes whose
    chunk no longer fits.
    """
    if tile_footprint_bytes((tm, tn, tk), _TILE_BYTES, acc_bytes) <= backend.vmem_budget_bytes():
        return 0.0
    return 2.0 * tm * tn * acc_bytes


def comp_step_time(kind: str, sig: Tuple[int, ...], world: int, cand: Candidate) -> float:
    """Per-step compute time for one candidate, tile blocking included.

    Every tunable kind prices its realized (tm, tn, tk) blocking (see
    :func:`realized_tile`) with a per-tile roofline: the GEMM kinds as in
    the module docstring; attention as a per-tile softmax+MXU roofline
    (score-tile MXU utilization, a VPU softmax term, score-spill bytes);
    MoE as per-expert tile occupancy (last-row-tile padding waste over the
    capacity-sized expert groups).  All terms are accum-dtype-free so AG
    flows keep the deterministic f32 tie-break.
    """
    _, flops = step_terms(kind, sig, world, cand.accum_dtype)
    sig = tuple(sig)
    nch = max(1, cand.num_channels)
    dims = _tile_dims(kind, sig, world, nch)
    if dims is None:
        return flops / backend.chip().peak_flops

    m, n, k = dims
    tm, tn, tk = realized_tile(kind, sig, world, cand)
    mxu = backend.mxu_dim()

    if kind in GEMM_TILE_KINDS:
        eff = (min(tm, mxu) / mxu) * (min(tn, mxu) / mxu)
        lead = max(1, abs(int(sig[0])))  # decode sigs negate the lead
        # all C channels run their blocks each step
        blocks_mn = (m // tm) * (n // tn) * nch * lead
        n_tiles = blocks_mn * (k // tk)
        # output tiles are written in the activation dtype — the MXU
        # accumulates f32 natively, so the wire dtype must not bias the
        # compute term (it already prices the wire for travelling partials)
        bytes_touched = (n_tiles * (tm * tk + tk * tn) + blocks_mn * tm * tn) * _TILE_BYTES
        bytes_touched += blocks_mn * _spill_bytes(tm, tn, tk, 4)
        t_flops = flops / (backend.chip().peak_flops * eff)
        t_mem = bytes_touched / backend.chip().hbm_bw
        return max(t_flops, t_mem) + BETA_TILE_S * n_tiles

    if kind == "ag_attention":
        b, h, _hkv, s_loc, d = sig
        # (tm, tk) block the (block_q, block_kv) score tile; tn clamps to the
        # head dim.  Per step each channel consumes one s_sub KV chunk for
        # every (batch, head).
        blocks = b * h * (m // tm) * (k // tk) * nch
        n_tiles = blocks * max(1, n // tn)
        eff = (min(tm, mxu) / mxu) * (min(tk, mxu) / mxu)  # QK^T -> (tm, tk)
        t_flops = flops / (backend.chip().peak_flops * eff)
        # softmax is VPU work over every score element, fp32 regardless of
        # the wire dtype (the compute term must stay accum-dtype-free)
        scores = float(b) * h * m * k * nch
        t_soft = _SOFTMAX_OPS * scores / (backend.chip().peak_flops * _VPU_FRACTION)
        # per block: Q tile + K and V tiles in, one accumulator update out;
        # a whole-chunk score tile that cannot stay resident spills fp32
        bytes_touched = blocks * (2.0 * tm * n + 2.0 * tk * n) * _TILE_BYTES
        bytes_touched += blocks * _spill_bytes(tm, tk, n, _SCORE_BYTES)
        t_mem = bytes_touched / backend.chip().hbm_bw
        return max(t_flops + t_soft, t_mem) + BETA_TILE_S * n_tiles

    # ag_moe / a2a_dispatch: per-expert grouped GEMMs over capacity-sized
    # token groups
    m_loc, d_model, top_k, e_loc, _d_exp = sig[:5]
    e_total = max(1, e_loc * world)
    m_sub = max(1, m_loc // nch)
    # per-expert row count: the capacity proxy (moe_overlap._capacity with
    # factor 1 — rounded up to the 8-row sublane)
    rows = max(8, ((m_sub * max(1, top_k) + e_total - 1) // e_total + 7) // 8 * 8)
    if len(sig) > 6:  # the signature's capacity axis caps the expert groups
        rows = min(rows, int(sig[6]))
    tm_e = min(tm, rows)
    row_tiles = -(-rows // tm_e)
    occupancy = rows / float(row_tiles * tm_e)  # last-row-tile padding waste
    blocks = e_loc * nch * row_tiles * max(1, n // tn)
    n_tiles = blocks * max(1, k // tk) * 2  # gate+up AND down projections
    eff = (min(tm_e, mxu) / mxu) * (min(tn, mxu) / mxu) * occupancy
    t_flops = flops / (backend.chip().peak_flops * eff)
    bytes_touched = (n_tiles * (tm_e * tk + tk * tn) + blocks * tm_e * tn) * _TILE_BYTES
    bytes_touched += blocks * _spill_bytes(tm_e, tn, tk, 4)
    t_mem = bytes_touched / backend.chip().hbm_bw
    return max(t_flops, t_mem) + BETA_TILE_S * n_tiles


def predict_cost(kind: str, sig: Tuple[int, ...], world: int, cand: Candidate) -> float:
    """Predicted makespan (seconds) of one candidate; lower is better."""
    wire, _ = step_terms(kind, sig, world, cand.accum_dtype, cand.flow)
    steps = world

    # per-link effective bytes for this tile order
    dirs = 2.0 if (cand.order == "bidir_ring" and cand.num_channels >= 2) else 1.0
    hops = _order_hops(cand.order, world)

    t_comm = wire * hops / (backend.chip().link_bw * dirs)
    t_comp = comp_step_time(kind, sig, world, cand)

    steady = (steps - 1) * max(t_comm, t_comp)
    fill = (t_comm + t_comp) / cand.num_channels
    launch = ALPHA_S * cand.num_channels * steps
    return steady + fill + launch


def _fill_drain_time(kind: str, sig: Tuple[int, ...], world: int, cand: Candidate) -> float:
    """The pipeline fill/drain term of one op's makespan (same math as
    ``predict_cost``'s ``fill``)."""
    wire, _ = step_terms(kind, sig, world, cand.accum_dtype, cand.flow)
    dirs = 2.0 if (cand.order == "bidir_ring" and cand.num_channels >= 2) else 1.0
    hops = _order_hops(cand.order, world)
    t_comm = wire * hops / (backend.chip().link_bw * dirs)
    t_comp = comp_step_time(kind, sig, world, cand)
    return (t_comm + t_comp) / cand.num_channels


def seam_saving(sig: Tuple[int, ...], world: int, cand: Candidate) -> float:
    """Modeled time the fused seam removes vs. the unfused pair (seconds).

    Unfused, the RS pipeline's drain and the AG pipeline's fill serialize at
    the operator-collective boundary — the exposed-collective seam.  Fused,
    the home segments hand off rank-locally and the two pipelines schedule
    against each other, so the shorter of the two fill/drain tails hides
    inside the longer one:

        saving = min(fill_drain(rs), fill_drain(ag))

    Strictly positive for every candidate, so a schedule-compatible fused
    seam is never modeled slower than the same candidate unfused.
    """
    sig_rs, sig_ag = seq_sigs(tuple(sig), world)
    return min(
        _fill_drain_time("matmul_rs", sig_rs, world, cand),
        _fill_drain_time("ag_matmul", sig_ag, world, cand),
    )


def predict_seq_cost(
    sig: Tuple[int, ...], world: int, cand: Candidate, *, fused: bool = True
) -> float:
    """Predicted makespan (seconds) of the RS -> AG seam under one shared
    candidate: the two per-op makespans, minus the seam overlap when fused."""
    sig_rs, sig_ag = seq_sigs(tuple(sig), world)
    total = predict_cost("matmul_rs", sig_rs, world, cand) + predict_cost(
        "ag_matmul", sig_ag, world, cand
    )
    if fused:
        total -= seam_saving(sig, world, cand)
    return total


def a2a_saving(sig: Tuple[int, ...], world: int, cand: Candidate) -> float:
    """Modeled time the overlapped dispatch/combine pipeline removes vs.
    running the two exchanges back to back (seconds).

    In the overlapped executor the combine of step ``s`` flies while the
    dispatch of step ``s + 1`` is in flight (``core/overlap.run_a2a_seq``),
    so — exactly like :func:`seam_saving` — the shorter half's fill/drain
    tail hides inside the longer one.  Strictly positive, so a legal
    overlapped plan is never modeled slower than the same candidate split.
    """
    d_sig, c_sig = a2a_sigs(tuple(sig), world)
    return min(
        _fill_drain_time("a2a_dispatch", d_sig, world, cand),
        _fill_drain_time("combine_rs", c_sig, world, cand),
    )


def predict_a2a_cost(
    sig: Tuple[int, ...], world: int, cand: Candidate, *, fused: bool = True
) -> float:
    """Predicted makespan (seconds) of the MoE dispatch -> combine exchange
    under one shared candidate: the two per-kind makespans, minus the
    overlap credit when fused.  ``fused=False`` models the unfused
    ``a2a_moe_baseline`` style split (dispatch fully lands, then combine)."""
    d_sig, c_sig = a2a_sigs(tuple(sig), world)
    total = predict_cost("a2a_dispatch", d_sig, world, cand) + predict_cost(
        "combine_rs", c_sig, world, cand
    )
    if fused:
        total -= a2a_saving(sig, world, cand)
    return total


def explain(kind: str, sig: Tuple[int, ...], world: int, cand: Candidate) -> Dict[str, float]:
    """Itemized terms for reports/benchmarks (same math as predict_cost)."""
    wire, flops = step_terms(kind, sig, world, cand.accum_dtype, cand.flow)
    ext = chunk_extent(kind, sig)
    out = {
        "wire_bytes_per_step": wire,
        "flops_per_step": flops,
        "chunk_extent": float(ext),
        "comp_step_s": comp_step_time(kind, sig, world, cand),
        "predicted_s": predict_cost(kind, sig, world, cand),
    }
    if _tile_dims(kind, tuple(sig), world, max(1, cand.num_channels)) is not None:
        out["realized_tile"] = realized_tile(kind, sig, world, cand)
    return out
