"""MoE block — expert parallelism over the model axis + TileLink AG+MoE ring.

Routing (dynamic mapping), dispatch, expert FFN and combine follow the paper's
Fig. 5 workload: the router fills the dynamic lookup tables; the overlapped
"ag_rs" tile plan in core/moe_overlap.py (an AG flow of token tiles + a
reduction riding the same permutes, run by the generic schedule executor)
gathers token chunks and reduce-scatters combined outputs while local experts
compute — under whatever design point the ``ParallelContext`` chooses, with
the per-expert grouped GEMMs blocked by the channel's CompSpec (tm, tn, tk)
tile.
Shared experts (DeepSeek-style) run as a dense TP MLP in parallel with the
routed path (paper §7.3 does the same for Qwen1.5's shared experts).

With ``ParallelContext(ep_axis=...)`` the routed path switches to true expert parallelism: the overlapped
dispatch/combine all-to-all (``pc.a2a_moe`` -> ``core/moe_overlap.a2a_moe``),
where token tiles and their routing tables exchange pairwise per step, local
experts' grouped GEMMs run on landed tiles while the next exchange is in
flight, and weighted partials return home along the reversed edge.

Expert count is padded up to a multiple of the EP degree; padding experts get
-inf router logits and are never selected (their weights receive zero gradient
structurally — no masks needed).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.moe_overlap import moe_router
from repro.nn.layers import rms_norm, he_init, cdiv, ACTS
from repro.nn import ffn as dense_ffn

__all__ = ["init", "specs", "apply_seq", "apply_decode", "padded_experts"]


def padded_experts(cfg, tp: int) -> int:
    return cdiv(cfg.moe.num_experts, tp) * tp


def init(key, cfg, tp: int, dtype=jnp.bfloat16):
    d = cfg.d_model
    m = cfg.moe
    e_pad = padded_experts(cfg, tp)
    f = m.d_expert
    ks = jax.random.split(key, 4)
    p = {
        "ln": jnp.zeros((d,), dtype),
        "router": he_init(ks[0], (d, e_pad), jnp.float32, fan_in=d),
        "w_gu": he_init(ks[1], (e_pad, d, 2 * f), dtype, fan_in=d),
        "w_down": he_init(ks[2], (e_pad, f, d), dtype, fan_in=f),
    }
    if m.num_shared:
        p["shared"] = dense_ffn.init(ks[3], cfg, tp, dtype,
                                     d_ff=m.num_shared * f)
    return p


def specs(cfg, tp: int, dp) -> dict:
    s = {
        "ln": P(None),
        "router": P(None, None),
        "w_gu": P("model", dp, None),
        "w_down": P("model", None, dp),
    }
    if cfg.moe.num_shared:
        s["shared"] = dense_ffn.specs(cfg, tp, dp)
    return s


def apply_seq(params, x, pc, cfg, *, next_proj=None):
    """x: [B, s_loc, D] -> ([B, s_loc, D], aux_loss). Inside manual region.

    Batch rows are routed/dispatched independently (vmap over B) so the
    DP-sharded batch dim partitions cleanly; capacity is per (batch row,
    sequence chunk).  The routed exchange and the shared-expert MLP take
    their design point from ``pc``.

    A context with an ``ep_axis`` runs the expert-parallel path
    (``pc.a2a_moe``: overlapped dispatch/combine all-to-all with the routing
    tables riding the token tiles); otherwise the TP AG+MoE double ring
    (``pc.ag_moe``).  Both paths share capacity/drop semantics.

    ``next_proj`` is accepted for keyword-surface symmetry with
    ffn/attention ``apply_seq`` but must be None: the MoE combine ends at
    the residual stream (a reduction, not a projection), so there is no
    RS -> AG seam to fuse into a downstream consumer.
    """
    if next_proj is not None:
        raise ValueError(
            "moe.apply_seq does not support next_proj: the MoE combine ends "
            "at the residual stream, so there is no RS->AG seam to fuse "
            "into a consumer")
    m = cfg.moe
    e_pad = params["w_gu"].shape[0] * pc.tp  # per-shard E_loc * tp
    h = rms_norm(x, params["ln"], cfg.norm_eps)

    def route(tok):
        return moe_router(tok, params["router"], num_experts=e_pad,
                          top_k=m.top_k, valid_experts=m.num_experts)

    ids, wts, aux = jax.vmap(route)(h)  # [B, s_loc, k], aux [B]
    moe_op = pc.a2a_moe if pc.ep_axis is not None else pc.ag_moe
    out = jax.vmap(
        lambda t, i, w: moe_op(t, i, w, params["w_gu"], params["w_down"],
                               capacity_factor=m.capacity_factor,
                               act=ACTS[cfg.act])
    )(h, ids, wts)
    # aux loss: mean over batch rows + ring members
    aux = jax.lax.pmean(aux.mean(), pc.axis)
    y = x + out.astype(x.dtype)
    if "shared" in params:
        y = dense_ffn.apply_seq(params["shared"], y, pc, cfg)  # residual inside
    return y, aux


def apply_decode(params, x, pc, cfg):
    """Decode: tokens replicated over model; local experts + psum combine.

    Bytes-optimal for small decode batches (§Perf): every LOCAL expert's
    weights are streamed from HBM exactly once and applied to all tokens with
    a masked combine — instead of per-(token, k) weight gathers, which read
    the same expert matrix up to m·k times.  Decode is memory-bound, so the
    extra (tiny-m) FLOPs are free and HBM traffic drops by ~m·k/E_loc.
    """
    m = cfg.moe
    e_loc = params["w_gu"].shape[0]
    e_pad = e_loc * pc.tp
    b, s, d = x.shape
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    tokens = h.reshape(b * s, d)
    ids, wts, _ = moe_router(
        tokens, params["router"], num_experts=e_pad, top_k=m.top_k,
        valid_experts=m.num_experts,
    )
    e_lo = pc.axis_index() * e_loc
    f = params["w_down"].shape[1]
    local = ids - e_lo
    valid = (local >= 0) & (local < e_loc)

    if getattr(pc, "moe_decode_stream", False):
        # §Perf optimized path: stream each local expert ONCE over all tokens
        # with a masked combine — HBM weight traffic / (m*k / E_loc)
        onehot = jax.nn.one_hot(jnp.where(valid, local, 0), e_loc,
                                dtype=jnp.float32) * valid[..., None]
        comb = jnp.einsum("mke,mk->me", onehot, wts).astype(x.dtype)
        hdn = jnp.einsum("md,edf->emf", tokens, params["w_gu"])
        a = ACTS[cfg.act](hdn[..., :f]) * hdn[..., f:]
        ye = jnp.einsum("emf,efd->emd", a.astype(x.dtype), params["w_down"])
        out = pc.psum(jnp.einsum("emd,me->md", ye, comb))
    else:
        # baseline: per-(token, k) weight gathers
        local_g = jnp.where(valid, local, 0).astype(jnp.int32)
        wg = params["w_gu"][local_g]  # [m, k, d, 2f]
        hdn = jnp.einsum("md,mkdf->mkf", tokens, wg)
        a = ACTS[cfg.act](hdn[..., :f]) * hdn[..., f:]
        wd = params["w_down"][local_g]  # [m, k, f, d]
        ye = jnp.einsum("mkf,mkfd->mkd", a.astype(x.dtype), wd)
        comb = (wts * valid.astype(jnp.float32)).astype(x.dtype)
        out = pc.psum(jnp.einsum("mkd,mk->md", ye, comb))
    y = x + out.reshape(b, s, d)
    if "shared" in params:
        y = dense_ffn.apply_decode(params["shared"], y, pc, cfg)
    return y
