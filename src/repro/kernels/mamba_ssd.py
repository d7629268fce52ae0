"""Mamba-2 SSD (state-space duality) — chunked-parallel form.

``ssd_chunked`` is the MXU-friendly O(L·Q) chunked algorithm (Dao & Gu 2024):
quadratic attention-like intra-chunk matmuls + a lax.scan over chunk states.
Oracle: kernels.ref.ssd_ref (sequential recurrence).  Used by the mamba2/zamba2
architectures; sub-quadratic in sequence length (long_500k shapes).

``ssd_intra_chunk`` is the Pallas kernel for the quadratic intra-chunk term
(the compute hot-spot), tiled per (batch·chunk, head) with fp32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import backend
from repro.backend import pl

__all__ = ["ssd_chunked", "ssd_intra_chunk"]


def ssd_chunked(x, dt, a_log, b, c, *, chunk: int = 64, h_init=None, return_state: bool = False):
    """Chunked SSD. Shapes as in ref.ssd_ref:

    x [B,L,H,P], dt [B,L,H] (positive), a_log [H], b/c [B,L,G,N] -> y [B,L,H,P].
    """
    bsz, length, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    q = min(chunk, length)
    orig_len = length
    if length % q:
        # pad to a chunk multiple with dt=0 steps (decay 1, zero input —
        # exact identity on the state), slice the output back
        pad = q - length % q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        length = length + pad
    nc = length // q

    a = -jnp.exp(a_log.astype(jnp.float32))  # [H] negative
    dt32 = dt.astype(jnp.float32)
    da = dt32 * a[None, None, :]  # [B,L,H] per-step log-decay
    bx = jnp.repeat(b, rep, axis=2).astype(jnp.float32)
    cx = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    xdt = x.astype(jnp.float32) * dt32[..., None]  # dt-weighted inputs

    # chunked views: [B, NC, Q, ...]
    def chunked(t):
        return t.reshape(bsz, nc, q, *t.shape[2:])

    da_c = chunked(da)  # [B,NC,Q,H]
    cum = jnp.cumsum(da_c, axis=2)  # within-chunk cumulative
    total = cum[:, :, -1]  # [B,NC,H] chunk log-decay
    x_c, b_c, c_c = chunked(xdt), chunked(bx), chunked(cx)

    # ---- intra-chunk (quadratic in Q, attention-like) ----
    # L[qi, qj] = exp(cum_qi - cum_qj) for qj <= qi
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,NC,Q,Q,H]
    mask = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(mask[None, None, :, :, None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("bcqhn,bckhn->bcqkh", c_c, b_c)  # C_q · B_k
    y_intra = jnp.einsum("bcqkh,bcqkh,bckhp->bcqhp", scores, decay, x_c)

    # ---- chunk states & inter-chunk scan ----
    # S_c = sum_k exp(total - cum_k) B_k ⊗ xdt_k   [B,NC,H,N,P]
    state_decay = jnp.exp(total[:, :, None, :] - cum)  # [B,NC,Q,H]
    s_c = jnp.einsum("bckhn,bckh,bckhp->bchnp", b_c, state_decay, x_c)

    h0 = jnp.zeros((bsz, h, n, p), jnp.float32) if h_init is None else h_init.astype(jnp.float32)

    def scan_fn(hprev, inp):
        s_chunk, tot = inp  # [B,H,N,P], [B,H]
        hnew = hprev * jnp.exp(tot)[..., None, None] + s_chunk
        return hnew, hprev

    (h_last, h_prevs) = jax.lax.scan(
        scan_fn,
        h0,
        (jnp.moveaxis(s_c, 1, 0), jnp.moveaxis(total, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # [B,NC,H,N,P]

    # ---- inter-chunk contribution ----
    y_inter = jnp.einsum("bcqhn,bcqh,bchnp->bcqhp", c_c, jnp.exp(cum), h_prevs)

    y = (y_intra + y_inter).reshape(bsz, length, h, p)[:, :orig_len].astype(x.dtype)
    if return_state:
        return y, h_last
    return y


# -----------------------------------------------------------------------------
# Pallas kernel for the intra-chunk quadratic term
# -----------------------------------------------------------------------------


def _ssd_intra_kernel(cum_ref, cb_ref, x_ref, o_ref, *, q: int):
    """One (batch-chunk, head) tile: y = (CB * exp(cum_i - cum_j) * tril) @ x.

    cum_ref: [1, q, 1] cumulative log-decay; cb_ref: [1, q, q] C·B scores;
    x_ref: [1, q, p] dt-weighted inputs; o_ref: [1, q, p].
    """
    cum = cum_ref[0].astype(jnp.float32)  # [q, 1]
    diff = cum - cum.reshape(1, q)  # [q, q] cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    g = cb_ref[0].astype(jnp.float32) * decay
    o_ref[0] = jax.lax.dot_general(
        g,
        x_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(cum, cb, xdt, *, interpret=False):
    """Intra-chunk SSD term. cum: [T, Q] (T = B*NC*H tiles), cb: [T, Q, Q],
    xdt: [T, Q, P] -> y: [T, Q, P]."""
    t, q = cum.shape
    p = xdt.shape[-1]
    return backend.pallas_call(
        functools.partial(_ssd_intra_kernel, q=q),
        name="mamba_ssd",
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, q, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, q), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, p), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, q, p), xdt.dtype),
        dimension_semantics=("parallel",),
        interpret=interpret,
    )(cum.reshape(t, q, 1), cb, xdt)
