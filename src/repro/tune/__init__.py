"""Design-space autotuner over the compiled frontend.

The paper's core argument (§3.1) is that communication and computation tune
*independently*: the best ``(tile order, channel count f_C, accum dtype —
and, under a quant-widened space, the wire dtype)``
on the comm half and the best ``(tm, tn, tk)`` consumer tile on the compute
half both change per shape and per mesh.  ``compile_overlap`` lowers any
explicit point of that space; this package chooses one:

    result = autotune("ag_matmul", signature=(1, 64, 32, 32), mesh=mesh)
    fn = compile_overlap("ag_matmul", result.channel)

    ch = resolve_channel("ag_matmul", shapes=(x.shape, w.shape), mesh=mesh,
                         space=JOINT_SPACE)           # per call shape
    fused, ch_rs, ch_ag = resolve_seq(shapes=..., mesh=mesh)   # RS->AG seam
    fused, ch_d, ch_c = resolve_a2a(shapes=..., mesh=mesh)     # EP MoE pair

or for every op of a model:

    ParallelContext(mesh=mesh, tune=True)             # every op resolves joint

The tuner sits above ``core/``: only ``parallel/context.py`` calls it from
the model stack (the ``tune-import`` lint rule holds that line).

``DEFAULT_SPACE`` sweeps the comm half only; ``JOINT_SPACE`` adds the
pruned compute-tile lattice (``tune/candidates.py``) — shape-, VMEM- and
MXU-alignment-constrained via the ``repro.backend`` hardware probes;
``QUANT_SPACE`` additionally opens the wire-dtype (flow) axis for the
``QUANT_WIRE_KINDS`` (``ParallelContext(tune=True, quant="auto")``).

Rankers
-------
``ranker="measure"``  times candidates through ``compile_overlap`` under
                      shard_map on the target mesh (``tune/measure.py``:
                      AOT-split compilation, (median, iqr) scores) — pruned
                      by the successive-halving early-exit sweep in
                      ``tune/sweep.py`` (``REPRO_TUNE_SWEEP*`` knobs; the
                      v3 cache record keeps the pruning ledger);
``ranker="model"``    ranks with the analytic bytes-on-wire vs. per-tile-FLOPs
                      cost model (``tune/cost.py``);
``ranker="auto"``     (default) measures on a real TPU target, models
                      otherwise — emulated-CPU wall time is not a perf signal
                      (ROADMAP), and model ranking is pure host arithmetic so
                      it is also safe *inside* a trace, where timing is
                      impossible.  ``REPRO_TUNE_RANKER`` overrides globally.

Both rankers walk ONE candidate enumerator (``tune/candidates.py``) and
share ONE cache schema (``tune/cache.py``): results persist per mesh
fingerprint (mesh shape + axis names + device kind + jax version + backend
target) under ``~/.cache/repro-tune`` (``REPRO_TUNE_CACHE`` overrides), and
a fingerprint hit never re-measures — except that an *explicit*
``ranker="measure"`` request upgrades a model-ranked record in place, so
pre-warming the cache with measured winners actually takes effect.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple

import jax

from repro.core.channels import BlockChannel
from repro.tune import cache as _cache
from repro.tune import cost as _cost
from repro.tune import measure as _measure
from repro.tune import sweep as _sweep
from repro.tune.candidates import (
    A2A_SEQ_KIND,
    COMP_TILE_LATTICE,
    DEFAULT_SPACE,
    GEMM_TILE_KINDS,
    JOINT_SPACE,
    MOE_SIG_KINDS,
    QUANT_SPACE,
    QUANT_WIRE_KINDS,
    SEQ_KIND,
    Candidate,
    Space,
    TUNABLE_KINDS,
    a2a_sigs,
    chunk_extent,
    comp_tile_candidates,
    enumerate_a2a_candidates,
    enumerate_candidates,
    enumerate_seq_candidates,
    seq_sigs,
    signature,
)

__all__ = [
    "autotune",
    "resolve_channel",
    "resolve_seq",
    "resolve_a2a",
    "TuneResult",
    "Space",
    "Candidate",
    "DEFAULT_SPACE",
    "JOINT_SPACE",
    "QUANT_SPACE",
    "COMP_TILE_LATTICE",
    "GEMM_TILE_KINDS",
    "QUANT_WIRE_KINDS",
    "TUNABLE_KINDS",
    "SEQ_KIND",
    "A2A_SEQ_KIND",
    "MOE_SIG_KINDS",
    "RANKERS",
    "CACHE_SCHEMA",
    "signature",
    "enumerate_candidates",
    "enumerate_seq_candidates",
    "enumerate_a2a_candidates",
    "seq_sigs",
    "a2a_sigs",
    "comp_tile_candidates",
    "chunk_extent",
]

RANKERS = ("auto", "measure", "model")
_ENV_RANKER = "REPRO_TUNE_RANKER"

# record-format version.  v1 (PR 3) records are comm-only (no ``comp_tile``);
# v2 (PR 4) records predate the measured-sweep stats and the attention/MoE
# compute-tile axes; v3 records predate the wire-dtype (flow) axis, so their
# winners were chosen from a space that could never trade wire bytes for
# quantization error.  Loading any older (or malformed) record re-tunes — a
# cheap model ranking — instead of guessing; it never crashes and never
# half-applies.
CACHE_SCHEMA = 4


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Winner of one search (or one cache hit)."""

    kind: str
    signature: Tuple[int, ...]
    candidate: Candidate
    channel: BlockChannel
    ranker: str  # ranker that PRODUCED the record
    score: float  # predicted seconds or measured median us
    cache_hit: bool
    fingerprint: Dict[str, Any]
    considered: int  # candidates enumerated (0 on a hit)
    score_iqr: float = 0.0  # measured noise estimate (us); 0.0 for the model
    sweep: Optional[Dict[str, Any]] = None  # pruning ledger (measured sweeps)


def _entry_key(kind: str, axis: str, world: int, sig: Sequence[int], space: Space) -> str:
    # axis + world are part of the key: one multi-axis mesh fingerprint can
    # host tunings along different axes with different ring sizes
    shape = ",".join(str(int(s)) for s in sig)
    return f"{kind}|axis={axis}|world={int(world)}|sig={shape}|space={space.digest()}"


def _tracing() -> bool:
    """Best-effort: are we inside a jax trace (timing would be meaningless)?"""
    try:
        probe = getattr(jax.core, "trace_state_clean", None)
        if probe is None:
            from jax._src import core as _src_core  # probed, version-moved

            probe = getattr(_src_core, "trace_state_clean", None)
        return not probe() if probe is not None else False
    except Exception:
        return False


def _wants_measure_upgrade(rec: Dict[str, Any], ranker: Optional[str], mesh) -> bool:
    """Should this hit re-rank?  An *explicit* ``ranker="measure"`` request
    (argument or ``REPRO_TUNE_RANKER``) landing on a model-ranked record —
    exactly the pre-warm flow the fallback warning recommends — must measure
    and overwrite, provided measurement is actually possible here.  Measured
    records satisfy every request; ``"auto"`` never forces a re-rank.
    """
    requested = ranker or os.environ.get(_ENV_RANKER)
    return (
        requested == "measure"
        and rec.get("ranker") == "model"
        and mesh is not None
        and not _tracing()
    )


def _parse_record(rec: Any) -> Optional[Dict[str, Any]]:
    """Validated view of a cache record, or None when it must re-tune.

    Every way a record can be unusable degrades identically — to a re-tune:
    a v1/v2 record from an older schema (whose winner was picked from a
    smaller joint space, pre sweep-stats), or a malformed record (junk file,
    hand-edited entry, torn write).  Nothing here may raise.
    """
    try:
        if int(rec.get("schema", 1)) != CACHE_SCHEMA:
            return None
        flow = rec.get("flow")
        cand = Candidate(
            order=rec["order"],
            num_channels=int(rec["num_channels"]),
            accum_dtype=rec["accum_dtype"],
            comp_tile=tuple(int(t) for t in rec["comp_tile"]),
            flow=None if flow is None else str(flow),
        )
        cand.channel("_probe")  # spec construction validates order/dtype/tile
        sweep = rec.get("sweep")
        return {
            "candidate": cand,
            "ranker": str(rec["ranker"]),
            "score": float(rec["score"]),
            "score_iqr": float(rec.get("score_iqr_us", 0.0)),
            "sweep": dict(sweep) if isinstance(sweep, dict) else None,
        }
    except (AttributeError, KeyError, TypeError, ValueError):
        return None


def _resolve_ranker(ranker: Optional[str], mesh) -> str:
    from repro import backend

    choice = ranker or os.environ.get(_ENV_RANKER) or "auto"
    if choice not in RANKERS:
        raise ValueError(f"unknown ranker {choice!r}; one of {RANKERS}")
    if choice == "auto":
        choice = "measure" if backend.target() == "tpu" else "model"
    if choice == "measure" and (mesh is None or _tracing()):
        warnings.warn(
            "repro.tune: measured ranking needs a mesh outside a trace; "
            "falling back to the analytic cost model (pre-tune with "
            "repro.tune.autotune(..., ranker='measure') to warm the cache)",
            stacklevel=3,
        )
        choice = "model"
    return choice


def autotune(
    kind: str,
    *,
    signature: Sequence[int],
    mesh=None,
    axis: str = "model",
    world: Optional[int] = None,
    base: Optional[BlockChannel] = None,
    ranker: Optional[str] = None,
    space: Space = DEFAULT_SPACE,
    cache_dir: Optional[str] = None,
    force: bool = False,
    repeats: int = 3,
    warmup: int = 1,
) -> TuneResult:
    """Find (or recall) the best design point for ``(kind, signature)``.

    ``signature`` is the canonical per-shard shape tuple (see
    :func:`repro.tune.signature`).  With ``mesh`` the fingerprint covers the
    whole topology; without one, ``world`` (the axis size) must be given.
    ``force=True`` re-ranks even on a cache hit (and overwrites the entry).
    """
    sig = tuple(int(s) for s in signature)
    if mesh is not None:
        world = int(mesh.shape[axis])
    if world is None:
        raise ValueError("autotune needs a mesh or an explicit world size")
    fp = _cache.mesh_fingerprint(mesh, axis=axis, world=world)
    key = _entry_key(kind, axis, world, sig, space)

    if not force:
        rec = _cache.load_entry(fp, key, directory=cache_dir)
        if rec is not None:
            rec = _parse_record(rec)  # old schema / malformed -> None (re-tune)
        if rec is not None and _wants_measure_upgrade(rec, ranker, mesh):
            rec = None  # explicit measure request upgrades a model-ranked entry
        if rec is not None:
            cand = rec["candidate"]
            return TuneResult(
                kind=kind,
                signature=sig,
                candidate=cand,
                channel=cand.channel(axis, base),
                ranker=rec["ranker"],
                score=rec["score"],
                cache_hit=True,
                fingerprint=fp,
                considered=0,
                score_iqr=rec["score_iqr"],
                sweep=rec["sweep"],
            )

    use = _resolve_ranker(ranker, mesh)
    cands = enumerate_candidates(
        kind, extent=chunk_extent(kind, sig), space=space, sig=sig, world=world
    )
    best_iqr = 0.0
    sweep_stats: Optional[Dict[str, Any]] = None
    if use == "measure":
        # one CaseTimer per search: operands are synthesized once and shared
        # by every candidate; compile time is AOT-split out of every score
        case = _measure.CaseTimer(kind, mesh, axis, sig)

        def timer(cand, *, repeats=repeats, warmup=warmup):
            return case.time(cand.channel(axis, base), repeats=repeats, warmup=warmup)

        sw = _sweep.measured_sweep(kind, sig, world, cands, timer, repeats=repeats, warmup=warmup)
        best, best_score, best_iqr = sw.winner, sw.median_us, sw.iqr_us
        sweep_stats = sw.stats
    else:
        best, best_score = None, float("inf")
        for cand in cands:
            score = _cost.predict_cost(kind, sig, world, cand)
            if score < best_score:  # strict: ties keep enumeration order
                best, best_score = cand, score
    assert best is not None

    record = {
        "schema": CACHE_SCHEMA,
        "kind": kind,
        "signature": list(sig),
        "world": world,
        "order": best.order,
        "num_channels": best.num_channels,
        "accum_dtype": best.accum_dtype,
        "comp_tile": list(best.comp_tile),
        "flow": best.flow,
        "ranker": use,
        "score": best_score,
        "score_unit": "us_measured" if use == "measure" else "s_predicted",
        "considered": len(cands),
    }
    if use == "measure":
        record["score_iqr_us"] = best_iqr
        record["sweep"] = sweep_stats
    _cache.store_entry(fp, key, record, directory=cache_dir)
    return TuneResult(
        kind=kind,
        signature=sig,
        candidate=best,
        channel=best.channel(axis, base),
        ranker=use,
        score=best_score,
        cache_hit=False,
        fingerprint=fp,
        considered=len(cands),
        score_iqr=best_iqr,
        sweep=sweep_stats,
    )


def resolve_seq(
    *,
    shapes: Optional[Sequence[Tuple[int, ...]]] = None,
    sig: Optional[Sequence[int]] = None,
    mesh=None,
    axis: str = "model",
    world: Optional[int] = None,
    base: Optional[BlockChannel] = None,
    ranker: Optional[str] = None,
    space: Space = DEFAULT_SPACE,
) -> Tuple[bool, BlockChannel, BlockChannel]:
    """Seam-aware resolution for the ``["matmul_rs", "ag_matmul"]`` seam.

    Returns ``(fused, ch_rs, ch_ag)``: whether to run the fused seam, and the
    channel for each half.  The fused plan is priced over the shared-channel
    candidates (``enumerate_seq_candidates``) with the eliminated
    exposed-collective time credited (``cost.seam_saving``); the unfused plan
    takes each half's own autotuned winner and prices the pair on the SAME
    modeled scale (``cost.predict_cost`` — never mixing measured us with
    modeled seconds).  Whenever a shared-channel candidate exists, the fused
    seam with *those* channels costs no more than the same channels unfused —
    unfused only wins here when the halves' independent winners diverge by
    more than the seam saving (e.g. extents that clamp a good shared C away).
    Pure host-side arithmetic plus cache-backed per-op lookups: trace-safe.
    """
    if sig is None:
        if shapes is None:
            raise ValueError("resolve_seq needs shapes or a signature")
        sig = signature(SEQ_KIND, [tuple(s) for s in shapes])
    sig = tuple(int(s) for s in sig)
    if world is None and mesh is not None:
        world = int(mesh.shape[axis])
    if world is None:
        raise ValueError("resolve_seq needs a mesh or an explicit world size")

    best_f, best_f_score = None, float("inf")
    for cand in enumerate_seq_candidates(sig=sig, world=world, space=space):
        score = _cost.predict_seq_cost(sig, world, cand, fused=True)
        if score < best_f_score:  # strict: ties keep enumeration order
            best_f, best_f_score = cand, score

    sig_rs, sig_ag = seq_sigs(sig, world)
    res_rs = autotune(
        "matmul_rs", signature=sig_rs, mesh=mesh, axis=axis, world=world,
        base=base, ranker=ranker, space=space,
    )
    res_ag = autotune(
        "ag_matmul", signature=sig_ag, mesh=mesh, axis=axis, world=world,
        base=base, ranker=ranker, space=space,
    )
    unfused_score = _cost.predict_cost(
        "matmul_rs", sig_rs, world, res_rs.candidate
    ) + _cost.predict_cost("ag_matmul", sig_ag, world, res_ag.candidate)

    if best_f is not None and best_f_score <= unfused_score:
        ch = best_f.channel(axis, base)
        return True, ch, ch
    return False, res_rs.channel, res_ag.channel


def resolve_a2a(
    *,
    shapes: Optional[Sequence[Tuple[int, ...]]] = None,
    sig: Optional[Sequence[int]] = None,
    mesh=None,
    axis: str = "model",
    world: Optional[int] = None,
    base: Optional[BlockChannel] = None,
    ranker: Optional[str] = None,
    space: Space = DEFAULT_SPACE,
    capacity_factor: Optional[float] = None,
    imbalance: Optional[float] = None,
) -> Tuple[bool, BlockChannel, BlockChannel]:
    """Joint resolution for the ``["a2a_dispatch", "combine_rs"]`` pair.

    Returns ``(fused, ch_dispatch, ch_combine)``: whether to run the
    overlapped expert-parallel pipeline, and the channel for each half.
    The overlapped program is priced over the shared-channel candidates
    (``enumerate_a2a_candidates`` — every point already model-checked as a
    full dispatch -> GEMM -> combine protocol) with the pipeline overlap
    credited (``cost.a2a_saving``); the split program prices the same
    exchange without the credit, which is what ``a2a_moe_baseline`` (bulk
    all_gather + psum_scatter) degrades to.  Because the credit is strictly
    positive, unfused only wins when NO legal shared-channel candidate
    exists (e.g. a world the order cannot schedule) — the baseline then
    keeps numerical parity while the verifier keeps its guarantees.

    ``capacity_factor``/``imbalance`` fold into the signature's quantized
    MoE workload axes (``signature(..., imbalance=, capacity=)``) so tight
    capacities and hot experts rank their own winners.  Pure host-side
    model arithmetic (the a2a halves have no single-op measured path), so
    this is trace-safe like :func:`resolve_seq`; ``ranker`` is accepted for
    signature symmetry and reserved for a future measured path.
    """
    del ranker  # model-ranked (see docstring)
    if world is None and mesh is not None:
        world = int(mesh.shape[axis])
    if world is None:
        raise ValueError("resolve_a2a needs a mesh or an explicit world size")
    if sig is None:
        if shapes is None:
            raise ValueError("resolve_a2a needs shapes or a signature")
        shapes = [tuple(s) for s in shapes]
        cap_rows = None
        if capacity_factor is not None:
            from repro.core.moe_overlap import _capacity

            m_loc, top_k, e_loc = shapes[0][-2], shapes[1][-1], shapes[3][0]
            cap_rows = _capacity(
                int(m_loc), int(top_k), max(1, int(e_loc) * world), float(capacity_factor)
            )
        sig = signature(A2A_SEQ_KIND, shapes, imbalance=imbalance, capacity=cap_rows)
    sig = tuple(int(s) for s in sig)

    best, best_score = None, float("inf")
    for cand in enumerate_a2a_candidates(sig=sig, world=world, space=space):
        score = _cost.predict_a2a_cost(sig, world, cand, fused=True)
        if score < best_score:  # strict: ties keep enumeration order
            best, best_score = cand, score

    if best is None:
        ch = base or BlockChannel(axis=axis)
        ch = ch.with_(axis=axis)
        return False, ch, ch
    ch = best.channel(axis, base)
    return True, ch, ch


def resolve_channel(
    kind: str,
    *,
    shapes: Optional[Sequence[Tuple[int, ...]]] = None,
    sig: Optional[Sequence[int]] = None,
    mesh=None,
    axis: str = "model",
    world: Optional[int] = None,
    base: Optional[BlockChannel] = None,
    ranker: Optional[str] = None,
    space: Space = DEFAULT_SPACE,
) -> BlockChannel:
    """Tuned ``BlockChannel`` for an op call — the transparent entry point.

    Cache hits and model ranking are pure host-side work, so this is safe at
    trace time (which is where ``ParallelContext(tune=True)`` calls it).
    Non-tuned fields (comm resource,
    mode, tiles) are inherited from ``base``.
    """
    if sig is None:
        if shapes is None:
            raise ValueError("resolve_channel needs shapes or a signature")
        sig = signature(kind, [tuple(s) for s in shapes])
    res = autotune(
        kind,
        signature=sig,
        mesh=mesh,
        axis=axis,
        world=world,
        base=base,
        ranker=ranker,
        space=space,
    )
    return res.channel
