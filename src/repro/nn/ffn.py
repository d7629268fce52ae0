"""Dense (gated) MLP block — the paper's motivational workload.

Forward = AG+GEMM (gate/up fused, column-parallel) -> activation ->
GEMM+RS (down, row-parallel): exactly the tensor-parallel MLP of paper Fig. 1.
In overlap mode both collectives lower through ``compile_overlap`` as tile
plans run by the generic schedule executor, under whatever design point the
``ParallelContext`` chooses for each op (``pc.channel``, or the tuner's
winner per shape under ``pc.tune``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn.layers import rms_norm, he_init, ACTS

__all__ = ["init", "specs", "apply_seq", "apply_decode", "seam_proj"]


def init(key, cfg, tp: int, dtype=jnp.bfloat16, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2 = jax.random.split(key)
    return {
        "ln": jnp.zeros((d,), dtype),
        "w_gu": he_init(k1, (d, 2 * f), dtype, fan_in=d),
        "w_down": he_init(k2, (f, d), dtype, fan_in=f),
    }


def specs(cfg, tp: int, dp) -> dict:
    return {"ln": P(None), "w_gu": P(dp, "model"), "w_down": P("model", dp)}


def _act(cfg):
    return ACTS[cfg.act]


def seam_proj(params, cfg):
    """(glue, w) pair for fusing an upstream RS into THIS block's gate/up AG.

    ``glue`` maps the upstream residual output to this block's AG input (the
    pre-MLP rms_norm); ``w`` is the column-parallel gate/up weight.  Pass the
    pair as the upstream op's ``next_proj`` and feed the fused output back in
    as this block's ``gu``.
    """
    return (lambda y: rms_norm(y, params["ln"], cfg.norm_eps)), params["w_gu"]


def apply_seq(params, x, pc, cfg, *, gu=None, next_proj=None):
    """x: [B, s_loc, D] -> [B, s_loc, D] (+residual). Inside manual region.

    Per-shard w_gu is [D, 2*f_loc] with gate|up halves interleaved per shard
    (column-parallel), so the activation is local.  The collectives' design
    point (channel, tuning, wire encoding) is ``pc``'s.

    Inter-op seam fusion (``pc.fuse_seams``): ``gu`` is this layer's gate/up
    projection already produced by the UPSTREAM op's fused RS->AG ring pass
    (skips the local norm + AG here); ``next_proj=(glue, w)`` asks this layer
    to fuse its down-proj RS with the NEXT consumer's AG over one shared ring
    pass — ``glue`` maps the full residual output to the consumer's AG input
    (e.g. the next layer norm) and ``w`` is the consumer's per-shard weight.
    With ``next_proj`` the return value is ``(y, next_out)``.
    """
    if gu is None:
        h = rms_norm(x, params["ln"], cfg.norm_eps)
        gu = pc.ag_matmul(h, params["w_gu"])  # AG + GEMM  [B, S, 2*f_loc]
    f_loc = gu.shape[-1] // 2
    a = _act(cfg)(gu[..., :f_loc]) * gu[..., f_loc:]
    a = a.astype(x.dtype)
    if next_proj is None:
        out = pc.matmul_rs(a, params["w_down"])  # GEMM + RS
        return x + out
    glue, w_next = next_proj
    # fused seam: down-proj RS flows into the consumer's AG in one ring pass
    return pc.matmul_rs_ag(a, params["w_down"], w_next, residual=x, glue=glue)


def apply_decode(params, x, pc, cfg):
    """x: [B, 1, D] replicated over model. Local matmuls + psum epilogue."""
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    gu = jnp.einsum("bsd,df->bsf", h, params["w_gu"])
    f_loc = gu.shape[-1] // 2
    a = _act(cfg)(gu[..., :f_loc]) * gu[..., f_loc:]
    out = pc.psum(jnp.einsum("bsf,fd->bsd", a.astype(x.dtype), params["w_down"]))
    return x + out
