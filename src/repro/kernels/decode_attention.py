"""Decode attention over the live rows of a stacked KV cache.

One query row per head (plain decode) reads only the spans of cache rows
that hold live rows of the slots that decode: the tile-centric idea applied
to the cache, where the work is cut into spans of rows and only the spans
that carry data are moved.

Layout: the cache stack is taken as its swapped view ``[U, B, G, hd, S]``
(layers, slots, KV heads, head dim, rows).  A TPU keeps a cache of head dim
64 with its rows minor, so this view is the bytes where they lie and the
kernel reads them in place.  Queries are ``[B, G, R, hd]``: query head
``g*R + r`` reads KV head ``g``.

The kernel runs once per call, with no grid: it lists the (slot, span)
work items of each slot's live row range in SMEM, then walks them in a loop
of manual copies, double buffered, so a slot that does not decode and a span
past a slot's length cost neither a copy nor a step.  It returns the
unnormalised online-softmax state ``(acc, m, l)`` of the rows it read (a
slot with no live row reads ``(0, -1e30, 0)``), for the caller to merge with
further keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro import backend
from repro.backend import pl

__all__ = ["decode_attention", "span_rows", "NEG_INF"]

NEG_INF = -1e30
SPAN = 512  # rows a span reads at most
_LANES = 128  # a span starts and ends on a whole (8, 128) tile of rows
_BUFFER_BYTES = 4 << 20  # VMEM for one K (or V) span buffer, both halves


def span_rows(size: int, kv_heads: int = 1, hd: int = 128, itemsize: int = 4) -> int:
    """Rows a span reads from a cache of ``size`` rows (a multiple of 128):
    the largest multiple of 128 that divides ``size``, is at most ``SPAN``,
    and keeps a double-buffered span of K within the kernel's buffer."""
    if size % _LANES:
        raise ValueError(f"cache rows {size} are not whole tiles of {_LANES}")
    fit = _BUFFER_BYTES // (2 * kv_heads * hd * itemsize)
    t = max(_LANES, min(SPAN, size, fit) // _LANES * _LANES)
    while size % t:
        t -= _LANES
    return t


def _kernel(layer_ref, lo_ref, hi_ref, q_ref, k_hbm, v_hbm, o_ref, m_ref, l_ref,
            kbuf, vbuf, sem, slot_ref, span_ref, *, t: int):
    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    layer, groups = layer_ref[0], kbuf.shape[1]

    # the work list, in SMEM: (slot, span) of each live span, slot by slot
    def add_slot(b, n):
        lo, hi = jnp.maximum(lo_ref[b], 0), jnp.minimum(hi_ref[b], k_hbm.shape[-1])
        first = lax.div(lo, t)
        last = jnp.where(hi > lo, lax.div(hi + t - 1, t), first)

        def add_span(k, n):
            slot_ref[n] = b
            span_ref[n] = k
            return n + 1

        return lax.fori_loop(first, last, add_span, n)

    n = lax.fori_loop(0, lo_ref.shape[0], add_slot, jnp.int32(0))

    def copies(w, buf):
        start = pl.multiple_of(span_ref[w] * t, _LANES)
        return [backend.make_async_copy(
                    ref.at[layer, slot_ref[w], :, :, pl.ds(start, t)],
                    dst.at[buf], sem.at[i, buf])
                for i, (ref, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    @pl.when(n > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def body(w, carry):
        buf = w % 2

        @pl.when(w + 1 < n)
        def _next():
            for c in copies(w + 1, 1 - buf):
                c.start()

        ck, cv = copies(w, buf)
        slot = slot_ref[w]
        rows = span_ref[w] * t + lax.broadcasted_iota(jnp.int32, (1, t), 1)
        live = (rows >= lo_ref[slot]) & (rows < hi_ref[slot])
        ck.wait()
        ps = []
        for g in range(groups):
            s = jnp.dot(q_ref[slot, g], kbuf[buf, g].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
            s = jnp.where(live, s, NEG_INF)  # [R, t]
            m_prev = m_ref[slot, g]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[slot, g] = alpha * l_ref[slot, g] + p.sum(axis=1, keepdims=True)
            m_ref[slot, g] = m_new
            ps.append((p, alpha))
        cv.wait()
        for g, (p, alpha) in enumerate(ps):
            pv = lax.dot_general(p, vbuf[buf, g].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 precision=lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)  # [R, hd]
            o_ref[slot, g] = alpha * o_ref[slot, g] + pv
        return carry

    lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, layer, lo, hi, *, interpret=None):
    """Online-softmax attention of one query row per head over cache rows
    ``[lo, hi)`` of each slot.

    q: [B, G, R, hd] f32, already scaled.  k, v: [U, B, G, hd, S], the
    stacked cache's swapped view, S a multiple of 128.  ``layer``: the index
    into U.  lo, hi: [B] int32, each slot's live rows; a slot with
    ``hi <= lo`` is skipped.  Both products run at full f32 precision
    and accumulate in f32.  Returns (acc [B, G, R, hd], m [B, G, R, 1],
    l [B, G, R, 1]), all f32; the attention output is ``acc / l``.
    ``interpret``: as :func:`repro.backend.resolve_interpret` takes it;
    a call inside a partially automatic shard_map asks for ``"hlo"``.
    """
    b, g, r, hd = q.shape
    size = k.shape[-1]
    t = span_rows(size, g, hd, k.dtype.itemsize)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def whole(shape):  # the whole array, in VMEM
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    state = (b, g, r, 1)
    return backend.pallas_call(
        functools.partial(_kernel, t=t),
        name="decode_attention",
        interpret=interpret,
        grid_spec=backend.prefetch_grid_spec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole(q.shape), hbm, hbm],
            out_specs=[whole(q.shape), whole(state), whole(state)],
            scratch_shapes=[backend.vmem_scratch((2, g, hd, t), k.dtype),
                            backend.vmem_scratch((2, g, hd, t), v.dtype),
                            backend.dma_semaphore((2, 2)),
                            backend.smem_scratch((b * size // t,)),
                            backend.smem_scratch((b * size // t,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in (q.shape, state, state)],
    )(layer, jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32), q.astype(jnp.float32), k, v)
