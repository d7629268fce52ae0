"""Flash-attention Pallas kernel (online softmax, GQA, causal, sliding window).

The compute half of the paper's Fig. 6 (AG-KV + self-attention): this kernel
consumes KV tiles in any arrival order the communication schedule produces;
tile-order independence comes from the online-softmax rescaling.

Layout: q [BH, Sq, D], k/v [BHkv, Sk, D].  Grid (BH, Sq/bq, Sk/bk), KV
innermost; m/l/acc VMEM scratch persists across the KV dimension.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import backend
from repro.backend import pl
from repro.core.comp_tiles import DEFAULT_TILE, largest_divisor

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _fa_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    bq: int,
    bk: int,
    n_kv: int,
    sq: int,
    sk: int,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions (queries right-aligned against keys, for decode/prefill)
    i = pl.program_id(1)
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: entirely-masked KV tiles do no work (tile-order freedom)
    run = True
    if causal:
        run = (j * bk) <= (i * bq + bq - 1 + (sk - sq))
    if window is not None:
        run = jnp.logical_and(run, (i * bq + (sk - sq) - (j * bk + bk - 1)) < window)

    @pl.when(run if isinstance(run, jnp.ndarray) else (run and True))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        # scores [bq, bk]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        mask = None
        if causal:
            mask = q_pos >= k_pos
        if window is not None:
            wm = (q_pos - k_pos) < window
            mask = wm if mask is None else jnp.logical_and(mask, wm)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p,
            v_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bk", "tile", "interpret"),
)
def flash_attention(
    q,
    k,
    v,
    *,
    causal=False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    bq=128,
    bk=128,
    tile: Optional[Tuple[int, int, int]] = None,
    interpret=False,
):
    """q: [BH, Sq, D], k/v: [BHkv, Sk, D] -> [BH, Sq, D].

    ``tile``: an optional CompSpec (tm, tn, tk) — the tuner's compute half.
    A non-default tile derives ``block_q``/``block_kv`` from (tm, tk),
    overriding ``bq``/``bk``; the (128, 128, 128) default is the
    backend-chosen sentinel and leaves them untouched.  Blocks clamp to
    divisors of the sequence extents (the shared largest-divisor rule), so
    any tuned tile runs instead of refusing on an awkward shape.
    """
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    rep = bh // bhkv
    scale = float(scale if scale is not None else d**-0.5)
    if tile is not None and tuple(tile) != DEFAULT_TILE:
        bq, bk = int(tile[0]), int(tile[2])
    bq = largest_divisor(sq, min(bq, sq))
    bk = largest_divisor(sk, min(bk, sk))
    n_kv = sk // bk

    kern = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        window=window,
        bq=bq,
        bk=bk,
        n_kv=n_kv,
        sq=sq,
        sk=sk,
    )
    return backend.pallas_call(
        kern,
        name="flash_attention",
        grid=(bh, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, rep=rep: (b // rep, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, rep=rep: (b // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            backend.vmem_scratch((bq, 1), jnp.float32),
            backend.vmem_scratch((bq, 1), jnp.float32),
            backend.vmem_scratch((bq, d), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q, k, v)
