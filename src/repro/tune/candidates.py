"""Candidate enumeration — ONE design-space walk for both rankers.

The tunable space is the full decoupled ``CommSpec x CompSpec`` surface the
plan layer sweeps (paper §3.1): tile order x channel count (f_C) x flow
dtype on the comm half, and the (tm, tn, tk) consumer-kernel tile on the
compute half.  Both the measured ranker and the analytic cost model iterate
the tuple returned by :func:`enumerate_candidates`, and the cache entry key
hashes the same :class:`Space` — so "which points were considered" is part
of a result's identity and a narrowed sweep can never shadow a full one.

Enumeration is deterministic (nested loops over the Space's ordered fields)
and feasibility-aware:

  * each requested channel count is pushed through
    ``mapping.effective_channels`` against the kind's chunked extent;
  * each requested compute tile is pruned against the operand shapes
    (largest-divisor clamp, like the comm half), the dtype-dependent MXU
    packing multiples, and the per-tile VMEM footprint — all probed through
    ``repro.backend`` (``sublane_multiple``, ``lane_multiple``,
    ``vmem_budget_bytes``), so tiles enumerated on an emulated host stay
    valid on real TPUs;
  * candidates that clamp onto an already-seen effective point are dropped —
    the rankers never time the same realized schedule twice.

``DEFAULT_SPACE`` sweeps the comm half only (the compute tile stays the
backend-chosen default) — the PR-3 contract.  ``JOINT_SPACE`` adds the
pruned (tm, tn, tk) lattice; ``ParallelContext(tune=True)`` searches it.
``QUANT_SPACE`` additionally opens the wire-dtype (flow) axis — ``QuantSpec``
per candidate, enumerated only for the ``QUANT_WIRE_KINDS`` — which
``ParallelContext(tune=True, quant="auto")`` searches.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Optional, Sequence, Tuple

from repro.core.channels import BlockChannel, ORDERS
from repro.core.comp_tiles import DEFAULT_TILE, resolve_tile, tile_footprint_bytes
from repro.core.mapping import effective_channels
from repro.core.quant import WIRE_DTYPES

__all__ = [
    "Space",
    "Candidate",
    "DEFAULT_SPACE",
    "JOINT_SPACE",
    "QUANT_SPACE",
    "COMP_TILE_LATTICE",
    "QUANT_WIRE_KINDS",
    "GEMM_TILE_KINDS",
    "SEQ_KIND",
    "A2A_SEQ_KIND",
    "MOE_SIG_KINDS",
    "enumerate_candidates",
    "enumerate_seq_candidates",
    "enumerate_a2a_candidates",
    "comp_tile_candidates",
    "signature",
    "seq_sigs",
    "a2a_sigs",
    "chunk_extent",
]

TUNABLE_KINDS = ("ag_matmul", "matmul_rs", "ag_attention", "ag_moe")

# the fused RS -> AG layer seam (compile_overlap seq form); tuned through its
# shared-channel enumerator + seam-aware cost, not the single-op paths above
SEQ_KIND = "seq_rs_ag"

# the expert-parallel MoE dispatch -> combine exchange (compile_overlap
# ["a2a_dispatch", "combine_rs"]); tuned through enumerate_a2a_candidates +
# cost.predict_a2a_cost, resolved jointly by tune.resolve_a2a
A2A_SEQ_KIND = "seq_a2a_moe"

# kinds whose signature may carry the optional trailing MoE workload axes
# (expert imbalance, capacity) — see signature()
MOE_SIG_KINDS = ("ag_moe", A2A_SEQ_KIND)

# kinds whose consumer compute is a plain GEMM the (tm, tn, tk) tile blocks
# directly; the attention and MoE consumers interpret the same tile through
# their own dims (see _tile_dims) — attention maps (tm, tk) onto
# (block_q, block_kv), MoE onto the per-expert grouped GEMMs
GEMM_TILE_KINDS = ("ag_matmul", "matmul_rs")

# kinds whose wire dtype is tunable (Space.flows).  The MoE kinds are
# excluded: their state carries int32 routing tables alongside the float
# tiles, so a quantized wire buys proportionally less and the executor's
# error story (re-encode per hop on the combine) is worse — the flow axis
# collapses to the inherited wire there.
QUANT_WIRE_KINDS = ("ag_matmul", "matmul_rs", "ag_attention")

# requested (tm, tn, tk) lattice of the joint space, default tile FIRST so a
# cost-model tie breaks toward the backend-chosen blocking.  Points are
# pruned per shape signature before ranking (see comp_tile_candidates).
COMP_TILE_LATTICE = (DEFAULT_TILE,) + tuple(
    (tm, tn, tk)
    for tm in (64, 128, 256)
    for tn in (128, 256, 512)
    for tk in (128, 256, 512)
    if (tm, tn, tk) != DEFAULT_TILE
)

# fraction of the probed VMEM budget one compute tile's working set may
# occupy (the rest holds the comm staging buffers and double-buffering)
VMEM_TILE_FRACTION = 0.25

# wire/operand bytes per element for footprint pruning (activations travel
# bf16 on TPU — same convention as tune/cost.py)
_IN_BYTES = 2


@dataclasses.dataclass(frozen=True)
class Space:
    """The swept portion of the design space (ordered -> deterministic)."""

    orders: Tuple[str, ...] = ORDERS
    channel_counts: Tuple[int, ...] = (1, 2, 4)
    accum_dtypes: Tuple[str, ...] = ("float32", "bfloat16")
    comp_tiles: Tuple[Tuple[int, int, int], ...] = (DEFAULT_TILE,)
    # wire-dtype (flow) axis: None = inherit the channel's QuantSpec (for a
    # bare channel, the accum dtype — legacy pricing).  Kept (None,) by
    # default so an existing sweep's identity does not change; widened by
    # QUANT_SPACE / ParallelContext(quant="auto").  Only the
    # QUANT_WIRE_KINDS enumerate it.
    flows: Tuple[Optional[str], ...] = (None,)

    def __post_init__(self):
        for o in self.orders:
            if o not in ORDERS:
                raise ValueError(f"unknown order {o!r}; one of {ORDERS}")
        if any(c < 1 for c in self.channel_counts):
            raise ValueError(f"channel counts must be >= 1: {self.channel_counts}")
        for t in self.comp_tiles:
            if len(t) != 3 or any(int(d) < 1 for d in t):
                raise ValueError(f"comp tiles must be 3 positive ints, got {t}")
        for f in self.flows:
            if f is not None and f not in WIRE_DTYPES:
                raise ValueError(f"unknown flow dtype {f!r}; one of {WIRE_DTYPES}")

    def digest(self) -> str:
        blob = repr(
            (self.orders, self.channel_counts, self.accum_dtypes, self.comp_tiles, self.flows)
        )
        return hashlib.sha256(blob.encode()).hexdigest()[:8]


DEFAULT_SPACE = Space()
JOINT_SPACE = Space(comp_tiles=COMP_TILE_LATTICE)
# the joint space with the wire-dtype axis opened: None first so a cost-model
# tie breaks toward the un-quantized wire (exactness wins ties)
QUANT_SPACE = Space(comp_tiles=COMP_TILE_LATTICE, flows=(None, "int8"))


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One design point; ``num_channels`` and ``comp_tile`` are already the
    effective (feasibility-clamped) values."""

    order: str
    num_channels: int
    accum_dtype: str
    comp_tile: Tuple[int, int, int] = DEFAULT_TILE
    # tuned wire dtype; None = keep the base channel's QuantSpec untouched
    flow: Optional[str] = None

    def channel(self, axis: str, base: Optional[BlockChannel] = None) -> BlockChannel:
        """Realize as a BlockChannel, inheriting non-tuned fields of ``base``."""
        base = base or BlockChannel(axis=axis)
        kw = {}
        if self.flow is not None:
            kw["quant"] = dataclasses.replace(base.quant, wire_dtype=self.flow)
        return base.with_(
            axis=axis,
            num_channels=self.num_channels,
            comm=dataclasses.replace(base.comm, order=self.order),
            comp=dataclasses.replace(
                base.comp, accum_dtype=self.accum_dtype, tile=tuple(self.comp_tile)
            ),
            **kw,
        )

    def label(self) -> str:
        tag = f"{self.order}/C={self.num_channels}/{self.accum_dtype}"
        if tuple(self.comp_tile) != DEFAULT_TILE:
            tm, tn, tk = self.comp_tile
            tag += f"/tile={tm}x{tn}x{tk}"
        if self.flow is not None:
            tag += f"/wire={self.flow}"
        return tag


def _tile_dims(
    kind: str, sig: Sequence[int], world: Optional[int], nch: int
) -> Optional[Tuple[int, int, int]]:
    """Per-step per-channel consumer extents (m, n, k) the tile must divide.

    GEMM kinds: the per-step GEMM itself.  ``ag_attention``: queries x head
    dim x per-channel KV rows — tm is block_q, tk is block_kv, tn clamps to
    the head dim (the flash-attention blocking).  ``ag_moe``: per-expert
    token rows x fused gate+up width x d_model (the first expert GEMM; the
    down projection reuses the same blocking, clamped to its own extents).
    Unknown kinds/signatures return None (the lattice collapses to the
    sentinel).
    """
    nch = max(1, nch)
    if kind == "ag_matmul":
        _, m_loc, k, n_loc = sig
        return max(1, m_loc // nch), n_loc, k
    if kind == "matmul_rs":
        _, m_glob, k_loc, n = sig
        m = max(1, m_glob // world) if world else m_glob
        return m, max(1, n // nch), k_loc
    if kind == "ag_attention":
        _b, _h, _hkv, s_loc, d = sig
        return s_loc, d, max(1, s_loc // nch)
    if kind in ("ag_moe", "a2a_dispatch"):
        # sig[:5] — MoE signatures may carry trailing (imbalance, capacity)
        # workload axes the tile lattice never reads
        m_loc, d_model, _top_k, _e_loc, d_exp = sig[:5]
        return max(1, m_loc // nch), 2 * d_exp, d_model
    return None


def comp_tile_candidates(
    kind: str,
    sig: Optional[Sequence[int]],
    *,
    world: Optional[int] = None,
    nch: int = 1,
    accum_dtype: str = "float32",
    space: Space = DEFAULT_SPACE,
) -> Tuple[Tuple[int, int, int], ...]:
    """Feasible (tm, tn, tk) points for one comm-half design point.

    Each requested tile is clamped to divisors of the per-step GEMM extents
    (largest-divisor rule, mirroring ``effective_channels``), then dropped if
    a clamped dim is neither the full extent nor a multiple of the MXU
    packing multiple for its position (sublane for tm/tk, lane for tn), or
    if the tile's VMEM working set exceeds ``VMEM_TILE_FRACTION`` of the
    probed budget.  ``DEFAULT_TILE`` is a sentinel ("backend-chosen
    blocking", what every op runs with when untuned) and passes through
    unclamped and unpruned.  A single-tile space is an *explicit* request
    (``Space(comp_tiles=(tile,))``): its point is clamped but
    never pruned — the kernels themselves clamp identically, so honoring it
    matches what an explicit channel would run.  Every tunable kind has a
    tile axis (the per-kind dims live in :func:`_tile_dims`); unknown kinds
    and signatures collapse to the sentinel.
    """
    import jax.numpy as jnp

    from repro import backend

    if sig is None:
        return (DEFAULT_TILE,)
    dims = _tile_dims(kind, tuple(int(s) for s in sig), world, nch)
    if dims is None:
        return (DEFAULT_TILE,)
    m, n, k = dims
    sub = backend.sublane_multiple(accum_dtype)
    lane = backend.lane_multiple()
    budget = int(backend.vmem_budget_bytes() * VMEM_TILE_FRACTION)
    acc_bytes = jnp.dtype(accum_dtype).itemsize

    def aligned(t: int, extent: int, mult: int) -> bool:
        return t == extent or t % mult == 0

    explicit = len(space.comp_tiles) == 1
    out, seen = [], set()
    for req in space.comp_tiles:
        req = tuple(int(d) for d in req)
        if req == DEFAULT_TILE:
            tile = DEFAULT_TILE  # sentinel: never clamped, never pruned
        else:
            tile = resolve_tile(req, m, n, k)
            tm, tn, tk = tile
            if not explicit:
                if not (aligned(tm, m, sub) and aligned(tn, n, lane) and aligned(tk, k, sub)):
                    continue
                if tile_footprint_bytes(tile, _IN_BYTES, acc_bytes) > budget:
                    continue
        if tile in seen:
            continue
        seen.add(tile)
        out.append(tile)
    if not out:
        # every lattice point was pruned (tiny budget / hostile extents):
        # fall back to the sentinel so the comm half stays tunable
        out.append(DEFAULT_TILE)
    return tuple(out)


def enumerate_candidates(
    kind: str,
    *,
    extent: Optional[int] = None,
    space: Space = DEFAULT_SPACE,
    sig: Optional[Sequence[int]] = None,
    world: Optional[int] = None,
) -> Tuple[Candidate, ...]:
    """Deterministic feasible design points for ``kind``.

    ``extent`` is the chunked extent ``num_channels`` must divide (see
    :func:`chunk_extent`); when known, infeasible counts are clamped through
    ``mapping.effective_channels`` and deduplicated.  ``sig``/``world``
    enable the compute-tile pruning (without them the comp axis passes
    through unclamped — extent-only callers keep the comm-only behavior).
    When ``world`` is known each (order, channels) point is also statically
    verified (``analysis.check_candidate``) so no measurement budget is ever
    spent on a schedule the executor would reject.
    """
    from repro.analysis import check_candidate

    if kind not in TUNABLE_KINDS:
        raise ValueError(f"kind {kind!r} is not tunable; one of {TUNABLE_KINDS}")
    flows = space.flows if kind in QUANT_WIRE_KINDS else (None,)
    out, seen = [], set()
    for order in space.orders:
        for req in space.channel_counts:
            if extent is not None:
                # warn=False: an enumerator probing feasibility is not a
                # surprise; the one-shot clamp warning stays armed for
                # genuine runtime fallbacks
                nch = effective_channels(extent, req, kind=kind, warn=False)
            else:
                nch = req
            if world is not None and check_candidate(kind, order, world, nch) is not None:
                continue  # provably illegal schedule: spend no budget on it
            for accum in space.accum_dtypes:
                if sig is not None:
                    tiles = comp_tile_candidates(
                        kind, sig, world=world, nch=nch, accum_dtype=accum, space=space
                    )
                else:
                    tiles = tuple(dict.fromkeys(tuple(int(d) for d in t) for t in space.comp_tiles))
                for tile in tiles:
                    for flow in flows:
                        cand = Candidate(
                            order=order, num_channels=nch, accum_dtype=accum,
                            comp_tile=tile, flow=flow,
                        )
                        if cand not in seen:
                            seen.add(cand)
                            out.append(cand)
    return tuple(out)


def _moe_axes(imbalance, capacity) -> Tuple[int, ...]:
    """Quantized optional MoE workload axes appended to a MoE signature.

    ``imbalance`` (hottest-expert load over the balanced mean, >= 1.0)
    quantizes to quarter-units so near-identical routing skews share one
    cache entry; ``capacity`` (per-expert row budget) quantizes up to the
    8-row sublane, matching ``moe_overlap._capacity``.  Capacity implies the
    imbalance slot (default balanced) so positions stay unambiguous:
    ``sig[5]`` is always imbalance, ``sig[6]`` always capacity.
    """
    if imbalance is None and capacity is None:
        return ()
    axes = (max(4, int(round(4.0 * float(1.0 if imbalance is None else imbalance)))),)
    if capacity is not None:
        axes += (max(8, -(-int(capacity) // 8) * 8),)
    return axes


def signature(kind: str, shapes: Sequence[Tuple[int, ...]], *,
              imbalance=None, capacity=None) -> Tuple[int, ...]:
    """Canonical shape signature from *per-shard* operand shapes.

    Takes the positional operand shapes exactly as the ``compile_overlap``
    ops receive them inside the manual region, and keeps only what changes
    the tuning landscape (leading batch dims collapse into one).

    MoE kinds (``ag_moe`` and the ``seq_a2a_moe`` pair) may append the
    optional quantized workload axes ``imbalance``/``capacity`` (see
    :func:`_moe_axes`): routing skew and capacity both move the tuning
    landscape (a hot expert gates the grouped GEMM; a tight capacity bounds
    it), so they are part of a result's identity.  Every signature consumer
    slices the shape half with ``sig[:5]``.
    """
    if (imbalance is not None or capacity is not None) and kind not in MOE_SIG_KINDS:
        raise ValueError(
            f"imbalance/capacity signature axes are defined for the MoE "
            f"kinds {MOE_SIG_KINDS}, not {kind!r}")

    def _lead(x):
        return math.prod(x[:-2]) if len(x) > 2 else 1

    if kind == SEQ_KIND:
        x, w1, w2 = shapes[0], shapes[1], shapes[2]
        # (lead, m_glob, k_loc, n_mid, n2_loc)
        return (_lead(x), x[-2], x[-1], w1[-1], w2[-1])
    if kind == "ag_matmul":
        x, w = shapes[0], shapes[1]
        return (_lead(x), x[-2], x[-1], w[-1])  # (lead, m_loc, k, n_loc)
    if kind == "matmul_rs":
        x, w = shapes[0], shapes[1]
        return (_lead(x), x[-2], x[-1], w[-1])  # (lead, m_glob, k_loc, n)
    if kind == "ag_attention":
        q, k = shapes[0], shapes[1]
        # s_loc comes from K: the KV shard is the ring extent — queries may
        # arrive gathered (the AG-Q + ring-KV layer form)
        return (q[0], q[1], k[1], k[2], q[3])  # (b, h, hkv, s_loc, d)
    if kind in MOE_SIG_KINDS:
        # ag_moe and the a2a pair take the same operand order
        # (x, topk_ids, topk_w, w_gu, w_down)
        x, ids, w_gu = shapes[0], shapes[1], shapes[3]
        # (m_loc, d_model, top_k, e_loc, d_expert) + optional workload axes
        base = (x[-2], x[-1], ids[-1], w_gu[0], w_gu[-1] // 2)
        return base + _moe_axes(imbalance, capacity)
    raise ValueError(f"kind {kind!r} is not tunable; one of {TUNABLE_KINDS}")


def seq_sigs(sig: Tuple[int, ...], world: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Split a seam signature into its constituent per-op signatures.

    The RS half sees the seam's inputs directly; the AG half consumes the
    reduce-scattered [m_glob / world, n_mid] home segment.
    """
    lead, m_glob, k_loc, n_mid, n2_loc = sig
    return (lead, m_glob, k_loc, n_mid), (lead, m_glob // world, n_mid, n2_loc)


def enumerate_seq_candidates(
    *,
    sig: Sequence[int],
    world: int,
    space: Space = DEFAULT_SPACE,
) -> Tuple[Candidate, ...]:
    """Shared-channel feasible design points for a fused RS -> AG seam.

    The seam handoff is per-channel, so only requests whose two chunked
    extents (RS: the n_mid columns, AG: the m_glob / world rows) clamp to the
    SAME effective count survive — anything else is what
    the ``compile_overlap`` seq form degrades to the unfused pair for.  Each
    surviving
    (order, C) point is statically verified as a seam
    (``analysis.check_seq_candidate``); compute tiles are pruned against the
    RS half's per-step GEMM (the dominant contraction at the seam).
    """
    from repro.analysis import check_seq_candidate

    sig = tuple(int(s) for s in sig)
    _lead, m_glob, _k_loc, n_mid, _n2_loc = sig
    if world < 1 or m_glob % world:
        return ()
    m_loc = m_glob // world
    sig_rs, _sig_ag = seq_sigs(sig, world)
    out, seen = [], set()
    for order in space.orders:
        for req in space.channel_counts:
            nch = effective_channels(n_mid, req, kind="matmul_rs", warn=False)
            if nch != effective_channels(m_loc, req, kind="ag_matmul", warn=False):
                continue
            if check_seq_candidate(order, world, nch) is not None:
                continue
            for accum in space.accum_dtypes:
                tiles = comp_tile_candidates(
                    "matmul_rs", sig_rs, world=world, nch=nch, accum_dtype=accum, space=space
                )
                for tile in tiles:
                    # both halves of the seam are QUANT_WIRE_KINDS, so the
                    # shared candidate enumerates the flow axis too
                    for flow in space.flows:
                        cand = Candidate(
                            order=order, num_channels=nch, accum_dtype=accum,
                            comp_tile=tile, flow=flow,
                        )
                        if cand not in seen:
                            seen.add(cand)
                            out.append(cand)
    return tuple(out)


def a2a_sigs(sig: Tuple[int, ...], world: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Split a ``seq_a2a_moe`` signature into its per-kind signatures.

    Unlike the RS -> AG seam, both halves of the MoE exchange see the SAME
    token extent — dispatch carries the tiles out, combine returns the
    weighted partials over the reverse of the same pairing — so both get the
    full signature (the combine's cost terms only read ``sig[:2]``).
    """
    sig = tuple(sig)
    return sig, sig


def enumerate_a2a_candidates(
    *,
    sig: Sequence[int],
    world: int,
    space: Space = DEFAULT_SPACE,
) -> Tuple[Candidate, ...]:
    """Shared-channel feasible design points for the MoE dispatch/combine
    exchange.

    Both halves chunk the same ``m_loc`` token extent, so (unlike the RS ->
    AG seam) every requested count clamps identically for the pair — there
    is no divergence case to degrade on.  Each surviving (order, C) point is
    statically verified as a full dispatch -> combine program
    (``analysis.check_a2a_candidate``: exchange legality, seam composition,
    protocol model check); compute tiles are pruned against the dispatch
    half's per-expert grouped GEMM.
    """
    from repro.analysis import check_a2a_candidate

    sig = tuple(int(s) for s in sig)
    m_loc = sig[0]
    if world < 1:
        return ()
    out, seen = [], set()
    for order in space.orders:
        for req in space.channel_counts:
            nch = effective_channels(m_loc, req, kind="a2a_dispatch", warn=False)
            if check_a2a_candidate(order, world, nch) is not None:
                continue
            for accum in space.accum_dtypes:
                tiles = comp_tile_candidates(
                    "a2a_dispatch", sig, world=world, nch=nch, accum_dtype=accum, space=space
                )
                for tile in tiles:
                    cand = Candidate(
                        order=order, num_channels=nch, accum_dtype=accum, comp_tile=tile
                    )
                    if cand not in seen:
                        seen.add(cand)
                        out.append(cand)
    return tuple(out)


def chunk_extent(kind: str, sig: Tuple[int, ...]) -> int:
    """The extent ``num_channels`` chunks for ``kind`` (what C must divide)."""
    if kind == "ag_matmul":
        return sig[1]  # m_loc rows of the local shard
    if kind == "matmul_rs":
        return sig[3]  # n columns of the partial
    if kind == "ag_attention":
        return sig[3]  # s_loc KV rows of the local shard
    if kind in ("ag_moe", "a2a_dispatch", "combine_rs"):
        return sig[0]  # m_loc token rows of the local chunk
    raise ValueError(f"kind {kind!r} is not tunable; one of {TUNABLE_KINDS}")
