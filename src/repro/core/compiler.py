"""TileLink frontend: compile ``(kind, BlockChannel)`` tile programs.

The paper's frontend takes (communication spec, computation spec, BlockChannel)
and emits a fused kernel.  ``compile_overlap`` is that entry point, and it is a
real (if small) compiler pipeline:

  1. **validate** — ``BlockChannel`` fields are checked at construction; the
     (kind, backend) pair is checked here, with one structured
     ``NotImplementedError`` for every unsupported combination;
  2. **plan** — ``core/plan.build_plan`` lowers the channel's CommSpec/CompSpec
     into a :class:`~repro.core.plan.TilePlan`: per-channel per-step peer
     schedules (from ``schedules.SCHEDULES``), flow permutations, flow kind,
     and the wire dtype (``plan.flow_dtype``, resolved from the channel's
     QuantSpec against its accum dtype).  Plans are cached on ``(kind,
     channel, world,
     num_channels)`` — ``plan.plan_cache_info()`` shows reuse;
  3. **execute** — one of two backends consumes the SAME plan:

     backend="xla"     the generic schedule executor (``core/overlap.run_plan``)
                       runs the plan over ``lax.ppermute`` — communication on
                       XLA async collectives ("copy engine"), compiles on any
                       platform incl. the 512-device dry-run.  All four kinds.
     backend="pallas"  fused Pallas kernels with explicit semaphores + remote
                       DMAs (``repro/kernels/ag_gemm.py``, ``gemm_rs.py``)
                       consume the plan's schedule tables — the literal
                       kernel-fusion analogue; runs on TPU, validated on CPU
                       via the ``repro.backend`` emulated target.

Because both backends execute the same plan, the whole ``CommSpec x CompSpec``
space (order x num_channels x accum_dtype x compute tile) is sweepable
uniformly across every kind — see ``benchmarks/kernel_bench.py --smoke``.

``channel="auto"`` autotunes instead of hard-coding a design point: the
returned callable resolves the best ``BlockChannel`` for its actual operand
shapes through ``repro.tune`` (persistent per-mesh cache; analytic cost model
at trace time, measured winners wherever the cache was pre-warmed — see
``repro/tune/__init__.py``), then lowers through the normal pipeline above.

``comp`` selects the *computation* half independently (the paper's decoupled
CompSpec): ``comp="auto"`` adds the pruned (tm, tn, tk) consumer-tile
lattice to the search — with ``channel="auto"`` the two halves are searched
jointly; with an explicit channel only the compute half is tuned, the comm
half held fixed.  An explicit ``CompSpec`` overrides the whole compute half
(tile AND accum dtype) without tuning; a bare (tm, tn, tk) tuple overrides
the tile ONLY, leaving the accum dtype to the channel (or, with
``channel="auto"``, to the comm search).

``quant`` selects the *wire* half (the :class:`~repro.core.quant.QuantSpec`
axis — what travels, decoupled from what accumulates): an explicit
``QuantSpec`` pins it on every candidate/channel; ``quant="auto"`` (or
``True``) opens the wire-dtype flow axis to the search
(``tune.QUANT_SPACE``-style, enumerated for the ``QUANT_WIRE_KINDS`` only),
so a comm-bound shape can resolve an int8 wire that beats the best
full-width candidate on modeled cost; ``None`` (default) keeps the
channel's own QuantSpec — the identity wire unless the caller set one.

``interpret=None`` defers to ``repro.backend.default_interpret()``: interpret
on CPU-only hosts, Mosaic on real TPUs.

The returned callable must be invoked inside shard_map over ``channel.axis``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Optional, Tuple, Union

import jax

from repro.core.channels import BlockChannel, CompSpec
from repro.core.quant import QuantSpec
from repro.core import overlap as _xla

__all__ = [
    "compile_overlap",
    "SeamFallbackWarning",
    "KINDS",
    "SEQ_KINDS",
    "BACKENDS",
    "PALLAS_KINDS",
    "unsupported_error",
    "scope_name",
]

KINDS = ("ag_matmul", "matmul_rs", "ag_attention", "ag_moe")
BACKENDS = ("xla", "pallas")
# kinds with a fused-kernel lowering; the others map their communication to
# the copy engine via host primitives (paper Fig. 5/6), i.e. backend="xla"
PALLAS_KINDS = ("ag_matmul", "matmul_rs")
# op sequences with a fused lowering (compile_overlap list form): the RS->AG
# layer seam and the expert-parallel MoE dispatch/combine pair
SEQ_KINDS = (("matmul_rs", "ag_matmul"), ("a2a_dispatch", "combine_rs"))
A2A_SEQ = ("a2a_dispatch", "combine_rs")


def scope_name(kinds) -> str:
    """The device scope an op's lowering runs under: ``overlap.<kind>``, or
    the kinds of a fused sequence joined by ``-``."""
    return "overlap." + (kinds if isinstance(kinds, str) else "-".join(kinds))


def _scoped(fn: Callable, kinds) -> Callable:
    """``fn`` traced under :func:`scope_name`, so that its ops keep one
    stable name in the compiled program whatever lowering was chosen."""
    name = scope_name(kinds)

    @functools.wraps(fn)
    def run(*args, **kw):
        with jax.named_scope(name):
            return fn(*args, **kw)

    return run


def unsupported_error(kind: str, backend: str) -> NotImplementedError:
    """The one structured error for every unsupported (kind, backend) pair."""
    supported = PALLAS_KINDS if backend == "pallas" else KINDS
    return NotImplementedError(
        f"compile_overlap: kind={kind!r} is not supported on "
        f"backend={backend!r} (supported there: {supported}); "
        "the paper maps this workload's communication to the copy engine "
        "(host primitives) — use backend='xla'"
    )


def _normalize_comp(comp) -> Union[None, str, CompSpec, Tuple[int, int, int]]:
    """None | "auto" | CompSpec | (tm, tn, tk).

    A bare tuple stays a tuple: it pins the TILE only, leaving the channel's
    (or the search's) accum dtype untouched; a full CompSpec pins the whole
    compute half (tile AND accum dtype).
    """
    if comp is None or comp == "auto":
        return comp
    if isinstance(comp, CompSpec):
        return comp
    if isinstance(comp, (tuple, list)) and len(comp) == 3:
        tile = tuple(int(t) for t in comp)
        if any(t < 1 for t in tile):
            raise ValueError(f"comp tile must be 3 positive ints, got {comp!r}")
        return tile
    raise ValueError(
        f"comp must be None, 'auto', a CompSpec, or a (tm, tn, tk) tuple, got {comp!r}"
    )


def _normalize_quant(quant) -> Union[None, str, QuantSpec]:
    """None | "auto" | QuantSpec (``True`` is shorthand for ``"auto"``)."""
    if quant is None or isinstance(quant, QuantSpec):
        return quant
    if quant is True or quant == "auto":
        return "auto"
    raise ValueError(
        f"quant must be None, 'auto'/True, or a QuantSpec, got {quant!r}"
    )


def compile_overlap(
    kind,
    channel: Union[BlockChannel, str, None] = None,
    *,
    comp=None,
    quant=None,
    backend: str = "xla",
    overlapped: bool = True,
    interpret: Optional[bool] = None,
    axis: str = "model",
    mesh=None,
    tune_ranker: Optional[str] = None,
    **kw,
) -> Callable:
    """Compile a tile program. See module docstring.

    ``kind`` is a single kind name, or a list/tuple of kinds (optionally
    ``(kind, channel)`` pairs) naming a fused op sequence — the supported
    sequences are ``["matmul_rs", "ag_matmul"]`` (the shared-ring layer seam)
    and ``["a2a_dispatch", "combine_rs"]`` (the expert-parallel MoE
    dispatch/combine pair).  ``channel`` is either an explicit :class:`BlockChannel` or
    the string ``"auto"`` (seq form also accepts None for the default
    channel); ``comp`` is None (use the channel's CompSpec), ``"auto"``
    (tune the compute half), or an explicit CompSpec / (tm, tn, tk) tuple;
    ``quant`` is None (use the channel's QuantSpec), ``"auto"``/``True``
    (open the wire-dtype flow axis to the search), or an explicit
    :class:`~repro.core.quant.QuantSpec` pin;
    ``axis``/``mesh``/``tune_ranker`` only apply to auto resolution (a mesh
    widens the tuning-cache fingerprint to the full topology).
    """
    if isinstance(kind, (list, tuple)):
        if comp is not None or interpret is not None:
            raise ValueError(
                "compile_overlap: comp/interpret apply to single-kind programs "
                "only; a seam sequence takes per-op (kind, channel) entries"
            )
        return _compile_seq(
            kind,
            channel=channel,
            backend=backend,
            overlapped=overlapped,
            axis=axis,
            mesh=mesh,
            tune_ranker=tune_ranker,
            quant=quant,
            **kw,
        )
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}; expected one of {KINDS}")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if backend == "pallas" and kind not in PALLAS_KINDS:
        # keep the unsupported-(kind, backend) contract loud at BUILD time —
        # no resolution mode (channel="auto", comp="auto") may defer it into
        # the first trace
        raise unsupported_error(kind, backend)
    comp = _normalize_comp(comp)
    quant = _normalize_quant(quant)
    if isinstance(channel, str):
        if channel != "auto":
            raise ValueError(f"channel must be a BlockChannel or 'auto', got {channel!r}")
        base = None
        if isinstance(comp, CompSpec):
            # pinned compute half, tuned comm half: the explicit CompSpec
            # fixes the tile AND the accum dtype; every candidate inherits it
            # through the base channel and the narrowed space built in
            # _auto_overlap
            base = BlockChannel(axis=axis, comp=comp)
        if isinstance(quant, QuantSpec):
            # pinned wire half: every candidate inherits it through the base
            # channel (the flow axis stays closed — nothing to search)
            base = (base or BlockChannel(axis=axis)).with_(quant=quant)
        return _auto_overlap(
            kind,
            backend=backend,
            overlapped=overlapped,
            interpret=interpret,
            axis=axis,
            mesh=mesh,
            tune_ranker=tune_ranker,
            comp=comp,
            quant="auto" if quant == "auto" else None,
            base=base,
            **kw,
        )
    if not isinstance(channel, BlockChannel):
        raise TypeError(f"channel must be a BlockChannel, got {type(channel)}")
    if isinstance(quant, QuantSpec):
        channel = channel.with_(quant=quant)
        quant = None
    if isinstance(comp, CompSpec):
        channel = channel.with_(comp=comp)
    elif isinstance(comp, tuple):
        # tile-only override: the channel's accum dtype is untouched
        channel = channel.with_(comp=dataclasses.replace(channel.comp, tile=comp))
    if comp == "auto" or quant == "auto":
        # explicit comm half, tuned compute and/or wire half: resolve per
        # call shapes with the channel's own comm point as the (only) comm
        # candidate
        return _auto_overlap(
            kind,
            backend=backend,
            overlapped=overlapped,
            interpret=interpret,
            axis=channel.axis,
            mesh=mesh,
            tune_ranker=tune_ranker,
            comp=comp if comp == "auto" else None,
            quant=quant,
            base=channel,
            **kw,
        )

    if backend == "xla":
        if kind == "ag_moe":
            from repro.core import moe_overlap

            fn = moe_overlap.ag_moe if overlapped else moe_overlap.ag_moe_baseline
        else:
            table = {
                ("ag_matmul", True): _xla.ag_matmul,
                ("ag_matmul", False): _xla.ag_matmul_baseline,
                ("matmul_rs", True): _xla.matmul_rs,
                ("matmul_rs", False): _xla.matmul_rs_baseline,
                ("ag_attention", True): _xla.ring_attention,
                ("ag_attention", False): _xla.ag_attention_baseline,
            }
            fn = table[(kind, overlapped)]
        fn = _scoped(fn, kind)
        if overlapped:
            # every overlapped kind lowers kind -> plan -> generic executor;
            # the plan itself is built (and cached) at trace time, once the
            # mesh world size is known inside shard_map
            return functools.partial(fn, axis=channel.axis, channel=channel, **kw)
        return functools.partial(fn, axis=channel.axis, **kw)

    # backend == "pallas"
    from repro import kernels as _k

    table = {
        "ag_matmul": _k.ag_gemm_shard,
        "matmul_rs": _k.gemm_rs_shard,
    }
    if kind not in table:
        raise unsupported_error(kind, backend)
    # interpret=None flows through to backend.resolve_interpret inside the
    # kernel's pallas_call — the target policy lives in one place only
    return functools.partial(_scoped(table[kind], kind), channel=channel,
                             interpret=interpret, **kw)


class SeamFallbackWarning(UserWarning):
    """A requested fused seam degraded loudly to the unfused op pair.

    Raised-as-warning exactly once per (axis, extents, channel-request) so a
    schedule-incompatible seam is never a silent perf cliff: the unfused pair
    is numerically identical, but the seam's collective time is exposed.
    """


_WARNED_SEAMS = set()


def _seam_incompatibility(ch_rs, ch_ag, world, m_glob, n_mid) -> Optional[str]:
    """Why this seam cannot fuse (None when it can).

    The fused executor hands each RS home segment to the AG half per channel,
    so both halves must resolve the SAME effective channel count — but RS
    chunks the N columns while AG chunks the M/R rows, and the two extents
    can clamp a shared request differently (or the ops may simply request
    different counts / run over different axes = different worlds).
    """
    from repro.core.mapping import effective_channels

    if ch_rs.axis != ch_ag.axis:
        return (
            f"producer runs over axis {ch_rs.axis!r} but consumer over "
            f"{ch_ag.axis!r} (mismatched worlds)"
        )
    if m_glob % world:
        return f"RS rows {m_glob} are not divisible by world {world}"
    nch_rs = effective_channels(n_mid, ch_rs.num_channels, kind="matmul_rs", warn=False)
    nch_ag = effective_channels(m_glob // world, ch_ag.num_channels, kind="ag_matmul", warn=False)
    if nch_rs != nch_ag:
        return (
            f"effective channel counts diverge: RS extent {n_mid} gives "
            f"C={nch_rs} (requested {ch_rs.num_channels}) but AG extent "
            f"{m_glob // world} gives C={nch_ag} (requested {ch_ag.num_channels})"
        )
    return None


def _warn_seam_fallback(reason: str, key) -> None:
    if key not in _WARNED_SEAMS:
        _WARNED_SEAMS.add(key)
        warnings.warn(
            SeamFallbackWarning(
                f"compile_overlap: seam is schedule-incompatible — {reason}; "
                "degrading to the unfused matmul_rs + ag_matmul pair (numerically "
                "identical, but the seam collective time is exposed)"
            ),
            stacklevel=3,
        )


def _seq_unfused(ch_rs, ch_ag, *, overlapped: bool, **kw) -> Callable:
    """The unfused reference composition with the same (y, ag_out) contract."""
    rs = compile_overlap("matmul_rs", ch_rs, backend="xla", overlapped=overlapped, **kw)
    ag = compile_overlap("ag_matmul", ch_ag, backend="xla", overlapped=overlapped, **kw)

    def pair_fn(x, w1, w2, *, residual=None, glue=None, **call_kw):
        out = rs(x, w1, **call_kw)
        y = out if residual is None else residual + out
        h = y if glue is None else glue(y)
        return y, ag(h, w2, **call_kw)

    return pair_fn


def _compile_seq(
    ops,
    *,
    channel: Union[BlockChannel, str, None] = None,
    backend: str = "xla",
    overlapped: bool = True,
    axis: str = "model",
    mesh=None,
    tune_ranker: Optional[str] = None,
    tune_base: Optional[BlockChannel] = None,
    tune_space=None,
    quant=None,
    **kw,
) -> Callable:
    """Compile a fused multi-op sequence (the ``compile_overlap`` list form).

    ``ops`` is a sequence of kind names or ``(kind, channel)`` pairs; the
    supported sequences are:

    ``["matmul_rs", "ag_matmul"]`` — the layer seam where a down/out
    projection's reduce-scatter hands its home segments directly to the next
    op's all-gather over one shared ring pass (``core/overlap.matmul_rs_ag``
    via ``core/plan.build_seq_plan``).  The returned callable has the
    signature

        fn(x, w1, w2, *, residual=None, glue=None) -> (y, ag_out)

    where ``y = residual + matmul_rs(x, w1)`` (the residual-stream value) and
    ``ag_out = ag_matmul(glue(y), w2)`` — ``glue`` is the rank-local seam
    elementwise (e.g. the consumer block's rms_norm), applied to the full
    home segment so the float ops match the unfused pair exactly.

    ``["a2a_dispatch", "combine_rs"]`` — the expert-parallel MoE pair: each
    step's direct pairwise exchange lands a peer's token tile + routing
    tables, the local experts' grouped GEMM runs while the next exchange is
    in flight, and the weighted partial returns home along the reversed edge
    (``core/moe_overlap.a2a_moe``).  The returned callable has the signature

        fn(x, topk_ids, topk_w, w_gu, w_down, *, capacity_factor=..., act=...)
            -> [m_loc, d]

    ``channel`` is a shared :class:`BlockChannel`, ``"auto"`` (the pair-aware
    tuner resolves both halves jointly per shape — ``repro.tune.resolve_seq``
    / ``resolve_a2a``), or None (the default channel); a per-op ``(kind,
    channel)`` entry overrides it for that op.  ``overlapped=False`` compiles
    the operator-centric unfused baseline pair (``a2a_moe_baseline`` for the
    MoE pair, with matching per-sub-chunk capacity semantics).

    If the RS->AG halves are schedule-incompatible at call time (mismatched
    worlds, or channel counts whose extents clamp differently), the call
    degrades LOUDLY to the unfused pair via one :class:`SeamFallbackWarning`
    — never a silent perf cliff, never a crash.  The a2a pair has no such
    cliff: both halves chunk the same token extent, so their effective
    channel counts always agree.
    """
    kinds, chans = [], []
    for op in ops:
        if isinstance(op, (tuple, list)):
            k, ch = op
        else:
            k, ch = op, channel
        kinds.append(k)
        chans.append(ch)
    kinds = tuple(kinds)
    if backend != "xla" or kinds not in SEQ_KINDS:
        raise NotImplementedError(
            f"compile_overlap: op sequence {kinds!r} is not supported on "
            f"backend={backend!r} (supported: {SEQ_KINDS} on backend='xla'); "
            "lower each op separately via single-kind compile_overlap calls"
        )
    quant = _normalize_quant(quant)
    if kinds == A2A_SEQ:
        return _compile_a2a(
            chans,
            channel=channel,
            overlapped=overlapped,
            axis=axis,
            mesh=mesh,
            tune_ranker=tune_ranker,
            tune_base=tune_base,
            tune_space=tune_space,
            quant=quant,
            **kw,
        )
    if any(ch == "auto" for ch in chans):
        base = next((ch for ch in chans if isinstance(ch, BlockChannel)), tune_base)
        if isinstance(quant, QuantSpec):
            base = (base or BlockChannel(axis=axis)).with_(quant=quant)
        elif quant == "auto":
            tune_space = _widen_flows(tune_space)
        return _auto_overlap_seq(
            axis=base.axis if base is not None else axis,
            mesh=mesh,
            tune_ranker=tune_ranker,
            base=base,
            space=tune_space,
            overlapped=overlapped,
            **kw,
        )
    ch_rs, ch_ag = (
        ch if isinstance(ch, BlockChannel) else BlockChannel(axis=axis) for ch in chans
    )
    if isinstance(quant, QuantSpec):
        ch_rs, ch_ag = ch_rs.with_(quant=quant), ch_ag.with_(quant=quant)
    elif quant == "auto":
        # quant-only search over explicit seam channels: pin the comm and
        # compute halves to the producer's point, search only the flow axis
        from repro.tune import Space as _Space

        return _auto_overlap_seq(
            axis=ch_rs.axis,
            mesh=mesh,
            tune_ranker=tune_ranker,
            base=ch_rs,
            space=_Space(
                orders=(ch_rs.comm.order,),
                channel_counts=(ch_rs.num_channels,),
                accum_dtypes=(ch_rs.comp.accum_dtype,),
                comp_tiles=(tuple(ch_rs.comp.tile),),
                flows=(None, "int8"),
            ),
            overlapped=overlapped,
            **kw,
        )
    if not overlapped:
        return _seq_unfused(ch_rs, ch_ag, overlapped=False, **kw)

    def seq_fn(x, w1, w2, *, residual=None, glue=None, **call_kw):
        import jax.numpy as jnp

        from repro import backend as _backend

        world = int(_backend.axis_size(ch_rs.axis))
        m_glob, n_mid = jnp.shape(x)[-2], jnp.shape(w1)[-1]
        reason = _seam_incompatibility(ch_rs, ch_ag, world, m_glob, n_mid)
        if reason is not None:
            _warn_seam_fallback(
                reason, (ch_rs.axis, ch_ag.axis, world, m_glob, n_mid,
                         ch_rs.num_channels, ch_ag.num_channels),
            )
            return _seq_unfused(ch_rs, ch_ag, overlapped=True, **kw)(
                x, w1, w2, residual=residual, glue=glue, **call_kw
            )
        return _scoped(_xla.matmul_rs_ag, kinds)(
            x, w1, w2,
            axis=ch_rs.axis, channel=ch_rs, channel2=ch_ag,
            residual=residual, glue=glue, **kw, **call_kw,
        )

    return seq_fn


def _widen_flows(space):
    """Open the wire-dtype flow axis on ``space`` (None = the default)."""
    from repro.tune import DEFAULT_SPACE

    return dataclasses.replace(space or DEFAULT_SPACE, flows=(None, "int8"))


def _compile_a2a(
    chans,
    *,
    channel,
    overlapped: bool,
    axis: str,
    mesh,
    tune_ranker: Optional[str],
    tune_base: Optional[BlockChannel] = None,
    tune_space=None,
    quant=None,
    **kw,
) -> Callable:
    """Compile the expert-parallel ``a2a_dispatch -> combine_rs`` pair.

    See :func:`_compile_seq` for the public contract.  Unlike the RS->AG seam
    there is no schedule-incompatibility fallback: both halves chunk the same
    local token extent, so their effective channel counts always agree and the
    a2a-seam invariants hold for every order (proven per ``build_seq_plan``
    miss).
    """
    from repro.core import moe_overlap

    if any(ch == "auto" for ch in chans):
        base = next((ch for ch in chans if isinstance(ch, BlockChannel)), tune_base)
        if isinstance(quant, QuantSpec):
            base = (base or BlockChannel(axis=axis)).with_(quant=quant)
        # quant="auto" is a no-op for the a2a pair: the MoE kinds are not
        # QUANT_WIRE_KINDS, so the enumerator never opens the flow axis there
        return _auto_overlap_a2a(
            axis=base.axis if base is not None else axis,
            mesh=mesh,
            tune_ranker=tune_ranker,
            base=base,
            space=tune_space,
            overlapped=overlapped,
            **kw,
        )
    ch_d, ch_c = (
        ch if isinstance(ch, BlockChannel) else BlockChannel(axis=axis) for ch in chans
    )
    if isinstance(quant, QuantSpec):
        ch_d, ch_c = ch_d.with_(quant=quant), ch_c.with_(quant=quant)
    if not overlapped:
        return functools.partial(
            _scoped(moe_overlap.a2a_moe_baseline, A2A_SEQ),
            axis=ch_d.axis,
            num_channels=ch_d.num_channels,
            **kw,
        )
    return functools.partial(
        _scoped(moe_overlap.a2a_moe, A2A_SEQ), axis=ch_d.axis, channel=ch_d,
        channel2=ch_c, **kw
    )


def _auto_overlap_a2a(
    *,
    axis: str,
    mesh,
    tune_ranker: Optional[str],
    base: Optional[BlockChannel],
    space=None,
    overlapped: bool,
    **kw,
) -> Callable:
    """Pair-aware auto resolution for the MoE dispatch/combine.

    ``repro.tune.resolve_a2a`` resolves both halves jointly (shared effective
    C, like seams) on the a2a cost model — per-step wire priced from the real
    peer hop counts of the order — and verdicts fused vs. the unfused
    AG+GroupGEMM+RS baseline per shape.
    """

    def auto_fn(x, topk_ids, topk_w, w_gu, w_down, **call_kw):
        import jax.numpy as jnp

        from repro import backend as _backend
        from repro.core import moe_overlap
        from repro.tune import resolve_a2a

        world = int(mesh.shape[axis]) if mesh is not None else int(_backend.axis_size(axis))
        resolve_kw = {} if space is None else {"space": space}
        fused, ch_d, ch_c = resolve_a2a(
            shapes=(
                jnp.shape(x),
                jnp.shape(topk_ids),
                jnp.shape(topk_w),
                jnp.shape(w_gu),
                jnp.shape(w_down),
            ),
            mesh=mesh,
            axis=axis,
            world=world,
            base=base,
            ranker=tune_ranker,
            capacity_factor=call_kw.get("capacity_factor"),
            **resolve_kw,
        )
        if fused and overlapped:
            fn = functools.partial(
                moe_overlap.a2a_moe, axis=axis, channel=ch_d, channel2=ch_c, **kw
            )
        else:
            fn = functools.partial(
                moe_overlap.a2a_moe_baseline,
                axis=axis,
                num_channels=ch_d.num_channels,
                **kw,
            )
        return _scoped(fn, A2A_SEQ)(x, topk_ids, topk_w, w_gu, w_down, **call_kw)

    return auto_fn


def _auto_overlap_seq(
    *,
    axis: str,
    mesh,
    tune_ranker: Optional[str],
    base: Optional[BlockChannel],
    space=None,
    overlapped: bool,
    **kw,
) -> Callable:
    """Seam-aware auto resolution: fused vs. unfused decided per shape.

    ``repro.tune.resolve_seq`` prices the fused seam (shared-C candidates,
    with the eliminated exposed-collective time credited) against the best
    unfused per-op pair on the same cost model and returns the cheaper plan;
    an unfused verdict here is a deliberate tuner decision, so no fallback
    warning is emitted on that path.
    """

    def auto_fn(x, w1, w2, *, residual=None, glue=None, **call_kw):
        import jax.numpy as jnp

        from repro import backend as _backend
        from repro.tune import resolve_seq

        world = int(mesh.shape[axis]) if mesh is not None else int(_backend.axis_size(axis))
        resolve_kw = {} if space is None else {"space": space}
        fused, ch_rs, ch_ag = resolve_seq(
            shapes=(jnp.shape(x), jnp.shape(w1), jnp.shape(w2)),
            mesh=mesh,
            axis=axis,
            world=world,
            base=base,
            ranker=tune_ranker,
            **resolve_kw,
        )
        fn = (
            _compile_seq(
                [("matmul_rs", ch_rs), ("ag_matmul", ch_ag)],
                overlapped=overlapped, axis=axis, **kw,
            )
            if fused
            else _seq_unfused(ch_rs, ch_ag, overlapped=overlapped, **kw)
        )
        return fn(x, w1, w2, residual=residual, glue=glue, **call_kw)

    return auto_fn


def _auto_overlap(
    kind: str,
    *,
    backend: str,
    overlapped: bool,
    interpret: Optional[bool],
    axis: str,
    mesh,
    tune_ranker: Optional[str],
    comp=None,
    quant=None,
    base=None,
    **kw,
) -> Callable:
    """Auto resolution: defer design-point choice to the operand shapes.

    Shapes are only known when the returned callable runs (inside shard_map,
    like every compiled op), so resolution happens there: a pure host-side
    cache lookup / cost-model ranking via ``repro.tune.resolve_channel`` —
    trace-safe — then the normal ``compile_overlap`` lowering.  The tuning
    cache memo makes repeated layer calls resolve once per (kind, shape).

    ``comp="auto"`` widens the search to the compute-tile lattice: jointly
    with the comm half when ``base`` is None, or comp-only (the base
    channel's comm point held fixed) when ``base`` is an explicit channel.
    ``quant="auto"`` opens the wire-dtype flow axis on top of whichever
    space the rest of the request selected (an explicit base channel with
    nothing else tuned pins the comm+comp halves, so only the flow axis is
    searched).
    """

    def auto_fn(*args, **call_kw):
        import jax.numpy as jnp

        from repro import backend as _backend
        from repro.tune import COMP_TILE_LATTICE, DEFAULT_SPACE, JOINT_SPACE, Space
        from repro.tune import resolve_channel

        world = int(mesh.shape[axis]) if mesh is not None else int(_backend.axis_size(axis))
        if isinstance(comp, CompSpec):
            # pinned compute half (tile + accum dtype), tuned comm half: the
            # single-tile space is honored (clamped, never pruned) and every
            # candidate inherits the rest of the CompSpec through ``base``
            space = Space(accum_dtypes=(comp.accum_dtype,), comp_tiles=(tuple(comp.tile),))
        elif isinstance(comp, tuple):
            # pinned tile only: the accum dtype stays part of the comm search
            space = Space(comp_tiles=(comp,))
        elif comp == "auto" and base is not None:
            space = Space(
                orders=(base.comm.order,),
                channel_counts=(base.num_channels,),
                accum_dtypes=(base.comp.accum_dtype,),
                comp_tiles=COMP_TILE_LATTICE,
            )
        elif comp == "auto":
            space = JOINT_SPACE
        elif quant == "auto" and base is not None:
            # quant-only search over an explicit channel: pin the comm and
            # compute halves to the base's own point
            space = Space(
                orders=(base.comm.order,),
                channel_counts=(base.num_channels,),
                accum_dtypes=(base.comp.accum_dtype,),
                comp_tiles=(tuple(base.comp.tile),),
            )
        else:
            space = DEFAULT_SPACE
        if quant == "auto":
            space = dataclasses.replace(space, flows=(None, "int8"))
        channel = resolve_channel(
            kind,
            shapes=[jnp.shape(a) for a in args],
            mesh=mesh,
            axis=axis,
            world=world,
            base=base,
            ranker=tune_ranker,
            space=space,
        )
        fn = compile_overlap(
            kind, channel, backend=backend, overlapped=overlapped, interpret=interpret, **kw
        )
        return fn(*args, **call_kw)

    return auto_fn
