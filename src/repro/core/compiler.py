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
     channel, world, num_channels)`` — ``plan.plan_cache_info()`` shows reuse;
  3. **execute** — one of two backends consumes the SAME plan:

     backend="xla"     the generic schedule executor (``core/overlap.run_plan``)
                       runs the plan over ``lax.ppermute`` — communication on
                       XLA async collectives ("copy engine"), compiles on any
                       platform.  All four kinds.
     backend="pallas"  fused Pallas kernels with explicit semaphores + remote
                       DMAs (``repro/kernels/ag_gemm.py``, ``gemm_rs.py``)
                       consume the plan's schedule tables — the literal
                       kernel-fusion analogue; runs on TPU, validated on CPU
                       via the ``repro.backend`` emulated target.

The channel is always explicit: this module lowers a design point, it never
chooses one.  Another compute tile or wire dtype is another channel
(``channel.with_(comp=...)``, ``channel.with_(quant=...)``).  Choosing the
channel per operand shape is the tuner's job (``repro.tune.resolve_channel``,
``resolve_seq``, ``resolve_a2a``), and ``ParallelContext(tune=True)`` is the
one place the model stack asks it.

``interpret=None`` defers to ``repro.backend.default_interpret()``: interpret
on CPU-only hosts, Mosaic on real TPUs.

The returned callable must be invoked inside shard_map over ``channel.axis``.
"""
from __future__ import annotations

import functools
import warnings
from typing import Callable, Optional

import jax

from repro.core.channels import BlockChannel
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

_TUNER = ("ParallelContext(tune=True), or repro.tune.resolve_channel / "
          "resolve_seq / resolve_a2a and then an explicit channel")
# keywords the frontend no longer takes -> what replaces each
_REMOVED = {
    "comp": "channel.with_(comp=CompSpec(...)) or channel.with_(comp="
            "dataclasses.replace(channel.comp, tile=...))",
    "quant": "channel.with_(quant=QuantSpec(...))",
    "axis": "BlockChannel(axis=...)",
    "mesh": _TUNER,
    "tune_ranker": _TUNER,
    "tune_base": _TUNER,
    "tune_space": _TUNER,
}


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


def _check_channel(channel, *, allow_none: bool) -> None:
    if isinstance(channel, str):
        raise ValueError(
            f"compile_overlap: channel={channel!r} — the frontend lowers an "
            f"explicit BlockChannel only; to choose one per shape use {_TUNER}"
        )
    if not (isinstance(channel, BlockChannel) or (allow_none and channel is None)):
        raise TypeError(f"channel must be a BlockChannel, got {type(channel)}")


def compile_overlap(
    kind,
    channel: Optional[BlockChannel] = None,
    *,
    backend: str = "xla",
    overlapped: bool = True,
    interpret: Optional[bool] = None,
    **kw,
) -> Callable:
    """Compile a tile program. See module docstring.

    ``kind`` is a single kind name, or a list/tuple of kinds (optionally
    ``(kind, channel)`` pairs) naming a fused op sequence — the supported
    sequences are ``["matmul_rs", "ag_matmul"]`` (the shared-ring layer seam)
    and ``["a2a_dispatch", "combine_rs"]`` (the expert-parallel MoE
    dispatch/combine pair).  ``channel`` is a :class:`BlockChannel`; the
    sequence form also accepts None for the default channel.  Other keywords
    are bound into the executor (e.g. ``causal``, ``capacity_factor``).
    """
    removed = next((k for k in _REMOVED if k in kw), None)
    if removed:
        raise TypeError(f"compile_overlap: {removed}= was removed; use {_REMOVED[removed]}")
    if isinstance(kind, (list, tuple)):
        if interpret is not None:
            raise ValueError(
                "compile_overlap: interpret applies to single-kind programs "
                "only; a seam sequence takes per-op (kind, channel) entries"
            )
        return _compile_seq(kind, channel=channel, backend=backend,
                            overlapped=overlapped, **kw)
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}; expected one of {KINDS}")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if backend == "pallas" and kind not in PALLAS_KINDS:
        raise unsupported_error(kind, backend)
    _check_channel(channel, allow_none=False)

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
    channel: Optional[BlockChannel] = None,
    backend: str = "xla",
    overlapped: bool = True,
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

    ``channel`` is a shared :class:`BlockChannel`, or None (the default
    channel over axis ``"model"``); a per-op ``(kind, channel)`` entry
    overrides it for that op.  ``overlapped=False`` compiles the
    operator-centric unfused baseline pair (``a2a_moe_baseline`` for the MoE
    pair, with matching per-sub-chunk capacity semantics).

    If the RS->AG halves are schedule-incompatible at call time (mismatched
    worlds, or channel counts whose extents clamp differently), the call
    degrades LOUDLY to the unfused pair via one :class:`SeamFallbackWarning`
    — never a silent perf cliff, never a crash.  The a2a pair has no such
    cliff: both halves chunk the same token extent, so their effective
    channel counts always agree.
    """
    kinds, chans = [], []
    for op in ops:
        k, ch = op if isinstance(op, (tuple, list)) else (op, channel)
        _check_channel(ch, allow_none=True)
        kinds.append(k)
        chans.append(ch if ch is not None else BlockChannel(axis="model"))
    kinds = tuple(kinds)
    if backend != "xla" or kinds not in SEQ_KINDS:
        raise NotImplementedError(
            f"compile_overlap: op sequence {kinds!r} is not supported on "
            f"backend={backend!r} (supported: {SEQ_KINDS} on backend='xla'); "
            "lower each op separately via single-kind compile_overlap calls"
        )
    if kinds == A2A_SEQ:
        return _compile_a2a(*chans, overlapped=overlapped, **kw)
    ch_rs, ch_ag = chans
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


def _compile_a2a(ch_d: BlockChannel, ch_c: BlockChannel, *, overlapped: bool,
                 **kw) -> Callable:
    """Compile the expert-parallel ``a2a_dispatch -> combine_rs`` pair.

    See :func:`_compile_seq` for the public contract.  Unlike the RS->AG seam
    there is no schedule-incompatibility fallback: both halves chunk the same
    local token extent, so their effective channel counts always agree and the
    a2a-seam invariants hold for every order (proven per ``build_seq_plan``
    miss).
    """
    from repro.core import moe_overlap

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
