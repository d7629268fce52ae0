"""ParallelContext — distribution configuration threaded through the model.

Carries the mesh, axis roles, the overlap mode, and the ``BlockChannel``
describing the communication/computation design point:

  mode="overlap"   TileLink tile plans run by the generic schedule executor
                   (compile_overlap -> core/plan -> core/overlap.run_plan)
  mode="baseline"  operator-centric AG/RS collectives — the non-overlap baseline
  (both run inside partial-auto shard_map, manual over the TP axis only;
   FSDP/DP axes stay under XLA's automatic partitioner)

This is the one place the model stack chooses an op's design point.  Layers
(`nn/attention.py`, `nn/ffn.py`, `nn/moe.py`, `nn/mamba.py`) call
``pc.ag_matmul`` / ``pc.matmul_rs`` / ``pc.ring_attention`` / ``pc.ag_moe`` /
``pc.a2a_moe`` / ``pc.matmul_rs_ag`` / ``pc.psum`` on *per-shard* values while
inside a manual region entered via ``pc.smap``; each op picks its channel here
and hands it to ``compile_overlap``, which only lowers it.

  tune=False   every op lowers ``pc.channel`` (order, channel count, accum
               dtype, compute tile, wire encoding);
  tune=True    in overlap mode each op asks the ``repro.tune`` autotuner for
               the best channel for its own operand shapes over the JOINT
               space — ``tune.resolve_channel`` for single ops,
               ``tune.resolve_seq`` for the RS->AG seam (fused or the unfused
               pair, per shape) and ``tune.resolve_a2a`` for the expert
               dispatch/combine (fused or the bulk baseline).  Cache hits and
               the cost model are host-side and trace-safe; measured winners
               apply wherever the cache was pre-warmed with
               ``tune.autotune(..., ranker="measure")``.  Fields of
               ``pc.channel`` the search does not cover are inherited.

``quant=`` pins a :class:`QuantSpec` on every op (baked into ``pc.channel``),
or ``quant="auto"`` opens the int8 wire axis to the tuner.  ``ep_axis=``
switches MoE layers to the expert-parallel all-to-all; ``fuse_seams=True``
has the model stack fuse each layer's down-projection RS into the next
consumer's AG over ONE shared ring pass (``pc.matmul_rs_ag``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat, tune
from repro.core.channels import BlockChannel
from repro.core.compiler import compile_overlap
from repro.core.quant import QuantSpec

__all__ = ["ParallelContext", "manual_only"]


def manual_only(spec: P, manual_axes: Tuple[str, ...]) -> P:
    """Strip a full PartitionSpec down to its manual-axis entries.

    P(('pod','data'), 'model') with manual=('model',) -> P(None, 'model').
    Used to derive shard_map in_specs from the global sharding table.
    """
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in manual_axes)
            return kept[0] if len(kept) == 1 else (kept if kept else None)
        return entry if entry in manual_axes else None

    return P(*(keep(e) for e in spec))


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    mesh: Any  # jax Mesh
    axis: str = "model"  # TP / SP / EP axis
    dp_axes: Tuple[str, ...] = ("pod", "data")
    mode: str = "overlap"  # "overlap" | "baseline"
    channel: BlockChannel = None
    attn_p_bf16: bool = False  # cast softmax P to bf16 before P@V
                                            # (halves attention HBM traffic)
    moe_decode_stream: bool = False  # stream local experts once over all
                                            # tokens in decode (bytes-optimal)
    tune: bool = False  # autotune each op's BlockChannel
                                            # per (kind, shape) via repro.tune
    tune_ranker: Optional[str] = None  # "measure" | "model" | "auto"/None
    fuse_seams: bool = False  # fuse layer RS->AG seams into one ring
                                            # pass (compile_overlap seq form)
    ep_axis: Optional[str] = None  # expert-parallel opt-in: mesh axis the
                                            # MoE dispatch/combine a2a runs
                                            # over (usually == axis)
    quant: Any = None  # wire-dtype policy: None (inherit channel),
                                            # a QuantSpec (pin every op's wire
                                            # encoding), or "auto"/True (open
                                            # the flow axis under tune=True)

    def __post_init__(self):
        if self.quant is True:
            object.__setattr__(self, "quant", "auto")
        if not (self.quant is None or self.quant == "auto"
                or isinstance(self.quant, QuantSpec)):
            raise ValueError(
                f"quant must be None, a QuantSpec, or 'auto'/True; "
                f"got {self.quant!r}")
        if self.ep_axis is not None and self.ep_axis not in dict(self.mesh.shape):
            raise ValueError(
                f"ep_axis {self.ep_axis!r} is not a mesh axis "
                f"(mesh axes: {tuple(dict(self.mesh.shape))})")
        if self.channel is None:
            object.__setattr__(self, "channel", BlockChannel(axis=self.axis))
        elif self.channel.axis != self.axis:
            # ops lower through compile_overlap(channel), which binds the
            # collective axis from the channel — a mismatch would run every
            # permute over a different axis than the manual region
            raise ValueError(
                f"BlockChannel.axis {self.channel.axis!r} != "
                f"ParallelContext.axis {self.axis!r}")
        if isinstance(self.quant, QuantSpec) and self.channel.quant != self.quant:
            # bake the pinned spec into the channel once: every op (tuned or
            # not) inherits the wire encoding from pc.channel from here on
            object.__setattr__(
                self, "channel", self.channel.with_(quant=self.quant))

    # ---- static topology -----------------------------------------------------
    @property
    def tp(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def dp(self) -> int:
        n = 1
        for a in self.dp_axes:
            if a in self.mesh.shape:
                n *= self.mesh.shape[a]
        return n

    def dp_spec(self):
        present = tuple(a for a in self.dp_axes if a in self.mesh.shape)
        return present if len(present) > 1 else (present[0] if present else None)

    # ---- ZeRO-3 use-time gather -------------------------------------------------
    def use_gather(self, tree, spec_tree):
        """Constrain parameters to drop DP/FSDP-axis sharding at use time.

        Storage keeps params sharded over (dp x model); this constraint makes
        XLA all-gather each layer's weights over the dp axes right before use
        (ZeRO-3), instead of contraction-partitioning the matmuls over dp
        (which would all-reduce activations — far more bytes).  The transpose
        of the gather reduce-scatters the gradients back to dp shards.
        """
        def one(a, s):
            return jax.lax.with_sharding_constraint(
                a, jax.sharding.NamedSharding(
                    self.mesh, manual_only(s, (self.axis,))))

        return jax.tree_util.tree_map(
            one, tree, spec_tree, is_leaf=lambda v: isinstance(v, P))

    # ---- manual-region entry ---------------------------------------------------
    def smap(self, fn: Callable, in_specs, out_specs) -> Callable:
        """Partial-auto shard_map, manual over the TP axis only."""
        return compat.shard_map(
            fn, self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_rep=False, axis_names={self.axis},
        )

    def manual(self, spec: P) -> P:
        return manual_only(spec, (self.axis,))

    # ---- per-shard collective ops (call inside smap) ---------------------------
    # every op lowers kind -> plan -> executor through the frontend; the plan
    # cache makes repeated layer calls reuse one schedule per design point
    @property
    def _tuned(self) -> bool:
        return self.tune and self.mode == "overlap"

    def _tune_space(self):
        """The JOINT space, widened with the int8 wire axis under quant='auto'."""
        if self.quant == "auto":
            return dataclasses.replace(tune.JOINT_SPACE, flows=(None, "int8"))
        return tune.JOINT_SPACE

    def _op(self, kind: str, shapes: Tuple = ()) -> Callable:
        channel = self.channel
        if self._tuned and shapes:
            # host-side: tuning-cache lookup / cost-model ranking (trace-safe);
            # the JOINT space searches both halves — comm (order, C, wire
            # dtype under quant="auto") and compute ((tm, tn, tk) consumer
            # tile) — per op shape
            channel = tune.resolve_channel(
                kind, shapes=shapes, mesh=self.mesh, axis=self.axis,
                base=self.channel, ranker=self.tune_ranker,
                space=self._tune_space())
        return compile_overlap(kind, channel, backend="xla",
                               overlapped=(self.mode == "overlap"))

    def ag_matmul(self, x, w, **kw):
        return self._op("ag_matmul", (jnp.shape(x), jnp.shape(w)))(x, w, **kw)

    def matmul_rs_ag(self, x, w1, w2, *, residual=None, glue=None, **kw):
        """Fused layer seam: matmul_rs(x, w1) -> ag_matmul(glue(residual + .), w2).

        One shared ring pass; each RS segment lands on its home rank and feeds
        the consumer's per-tile compute directly (no exposed collective at the
        seam).  With ``tune=True`` the seam-aware tuner prices fused vs.
        unfused per shape, and an unfused verdict composes the two ops; a
        schedule-incompatible seam degrades loudly to the unfused pair via one
        SeamFallbackWarning.  Returns ``(y, out)`` where
        ``y = residual + rs_out`` (pre-glue, for the residual stream) and
        ``out`` is the consumer's AG-matmul output.
        """
        ch_rs = ch_ag = self.channel
        if self._tuned:
            fused, ch_rs, ch_ag = tune.resolve_seq(
                shapes=(jnp.shape(x), jnp.shape(w1), jnp.shape(w2)),
                mesh=self.mesh, axis=self.axis, base=self.channel,
                ranker=self.tune_ranker, space=self._tune_space())
            if not fused:
                # the tuner's own verdict, not a fallback: no warning
                out = compile_overlap("matmul_rs", ch_rs)(x, w1, **kw)
                y = out if residual is None else residual + out
                h = y if glue is None else glue(y)
                return y, compile_overlap("ag_matmul", ch_ag)(h, w2, **kw)
        fn = compile_overlap([("matmul_rs", ch_rs), ("ag_matmul", ch_ag)],
                             overlapped=(self.mode == "overlap"))
        return fn(x, w1, w2, residual=residual, glue=glue, **kw)

    def matmul_rs(self, x, w, **kw):
        return self._op("matmul_rs", (jnp.shape(x), jnp.shape(w)))(x, w, **kw)

    def ring_attention(self, q, k, v, **kw):
        return self._op("ag_attention",
                        (jnp.shape(q), jnp.shape(k), jnp.shape(v)))(q, k, v, **kw)

    def ag_moe(self, x, ids, wts, w_gu, w_down, **kw):
        return self._op(
            "ag_moe", (jnp.shape(x), jnp.shape(ids), jnp.shape(wts),
                       jnp.shape(w_gu), jnp.shape(w_down)),
        )(x, ids, wts, w_gu, w_down, **kw)

    def a2a_moe(self, x, ids, wts, w_gu, w_down, **kw):
        """Expert-parallel MoE: overlapped dispatch/combine all-to-all.

        Lowers the ``["a2a_dispatch", "combine_rs"]`` pair through
        ``compile_overlap`` over ``ep_axis``: each step's direct pairwise
        exchange lands a peer's token tile + routing tables, the local
        experts' grouped GEMM runs while the next exchange is in flight, and
        the weighted partial returns home along the reversed edge.  Requires
        ``ParallelContext(ep_axis=...)`` — expert parallelism is opt-in.
        ``mode="baseline"`` (or an unfused tuner verdict under ``tune=True``)
        runs ``a2a_moe_baseline`` with identical capacity/drop semantics.
        """
        if self.ep_axis is None:
            raise ValueError(
                "a2a_moe requires ParallelContext(ep_axis=...); expert "
                "parallelism is opt-in (use ag_moe for the TP MoE path)")
        ch_d = ch_c = self.channel if self.ep_axis == self.axis else (
            self.channel.with_(axis=self.ep_axis))
        overlapped = self.mode == "overlap"
        if self._tuned:
            # the a2a MoE kinds are not QUANT_WIRE_KINDS, so the widened
            # space's flow axis is inert here (int32 routing tables dilute
            # the win)
            overlapped, ch_d, ch_c = tune.resolve_a2a(
                shapes=tuple(jnp.shape(a) for a in (x, ids, wts, w_gu, w_down)),
                mesh=self.mesh, axis=self.ep_axis, base=ch_d,
                ranker=self.tune_ranker, space=self._tune_space(),
                capacity_factor=kw.get("capacity_factor"))
        fn = compile_overlap([("a2a_dispatch", ch_d), ("combine_rs", ch_c)],
                             overlapped=overlapped)
        return fn(x, ids, wts, w_gu, w_down, **kw)

    def psum(self, x):
        return lax.psum(x, self.axis)

    def axis_index(self):
        return lax.axis_index(self.axis)

    def all_gather_seq(self, x, dim: int):
        return lax.all_gather(x, self.axis, axis=dim, tiled=True)
