"""GQA attention block — TP over heads, sequence-parallel residual stream.

Train/prefill path (``apply_seq``): the AG+GEMM producer gathers the
sequence-sharded residual stream while projecting to this rank's heads (the
paper's AG+GEMM), attention runs locally on the head shard with a
memory-efficient chunked online-softmax (differentiable), and the output
projection is the GEMM+RS consumer (paper Fig. 4).  Both collectives lower
through ``compile_overlap`` as tile plans, under whatever design point the
``ParallelContext`` chooses for each op (``pc.channel``, or the tuner's winner
per shape under ``pc.tune``).

Decode path (``apply_decode``): activations are replicated over the TP axis;
projections are local column/row-parallel matmuls with a psum epilogue, and the
KV cache is sharded over heads.

Awkward GQA head counts (kv < tp, non-dividing heads) are handled by the
GQALayout padding/replication scheme in nn/layers.py; padded weights are
grad-masked so semantics match the unpadded architecture exactly.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import backend
from repro.backend import shard_map
from repro.kernels.decode_attention import NEG_INF, decode_attention, span_rows
from repro.nn.layers import (
    rms_norm, rope, he_init, gqa_layout, GQALayout,
)

__all__ = [
    "init", "specs", "grad_masks", "apply_seq", "apply_seq_ring", "apply_decode",
    "init_cache", "chunked_attention", "seam_proj", "write_rows",
    "decode_kernel_takes", "decode_read_span",
]


# the lanes of a TPU tile, along ``max_len``: write_rows reads and writes
# back spans of cache rows that start at a multiple of this
_SPAN = 128


def _lay(cfg, tp) -> GQALayout:
    return gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)


def init(key, cfg, tp: int, dtype=jnp.bfloat16):
    lay = _lay(cfg, tp)
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 4)
    # orig-shaped kv weights, expanded with `rep` identical copies
    wkv_orig = he_init(ks[1], (d, lay.kv_pad, 2 * hd), dtype, fan_in=d)
    # zero the padded kv heads (stay zero via grad masks)
    kv_mask = (jnp.arange(lay.kv_pad) < cfg.n_kv_heads)[None, :, None]
    wkv_orig = wkv_orig * kv_mask
    wkv = jnp.repeat(wkv_orig, lay.rep, axis=1).reshape(d, lay.kv_store * 2 * hd)

    head_active = jnp.arange(lay.h_pad) < cfg.n_heads
    wq = he_init(ks[0], (d, lay.h_pad, hd), dtype, fan_in=d)
    wq = (wq * head_active[None, :, None]).reshape(d, lay.h_pad * hd)

    wo = he_init(ks[2], (lay.h_pad, hd, d), dtype, fan_in=lay.h_pad * hd)
    wo = (wo * head_active[:, None, None]).reshape(lay.h_pad * hd, d)
    p = {"ln": jnp.zeros((d,), dtype), "wq": wq, "wkv": wkv, "wo": wo}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((lay.h_pad * hd,), dtype)
        p["bkv"] = jnp.zeros((lay.kv_store * 2 * hd,), dtype)
    return p


def specs(cfg, tp: int, dp) -> dict:
    s = {
        "ln": P(None),
        "wq": P(dp, "model"),
        "wkv": P(dp, "model"),
        "wo": P("model", dp),
    }
    if cfg.qkv_bias:
        s["bq"] = P("model")
        s["bkv"] = P("model")
    return s


def grad_masks(cfg, tp: int):
    """0/1 masks keeping padded heads at zero. None entries = no mask."""
    lay = _lay(cfg, tp)
    hd = cfg.hd
    if lay.h_pad == cfg.n_heads and lay.kv_pad == cfg.n_kv_heads:
        return None
    qm = jnp.repeat((jnp.arange(lay.h_pad) < cfg.n_heads), hd).astype(jnp.float32)
    kv_head_active = jnp.arange(lay.kv_store) // lay.rep < cfg.n_kv_heads
    kvm = jnp.repeat(kv_head_active, 2 * hd).astype(jnp.float32)
    m = {
        "ln": None,
        "wq": qm[None, :],
        "wkv": kvm[None, :],
        "wo": qm[:, None],
    }
    if cfg.qkv_bias:
        m["bq"] = qm
        m["bkv"] = kvm
    return m


def chunked_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                      chunk: int = 1024, q_offset=0, scale: Optional[float] = None,
                      p_bf16: bool = False):
    """Memory-efficient online-softmax attention (differentiable).

    q: [B, H, Sq, hd]; k/v: [B, Hkv, Sk, hd] with H % Hkv == 0.
    Scans KV chunks with a rematerialized per-chunk body: O(Sq * chunk) live
    memory forward and backward.
    """
    b, h, sq, hd = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = scale if scale is not None else hd ** -0.5
    chunk = min(chunk, sk)
    assert sk % chunk == 0
    nc = sk // chunk

    q32 = (q * scale).astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)

    kc = k.reshape(b, hkv, nc, chunk, hd)
    vc = v.reshape(b, hkv, nc, chunk, hd)

    @jax.checkpoint
    def body(carry, kj, vj, cidx):
        m_i, l_i, o_i = carry
        if rep > 1:
            kj = jnp.repeat(kj, rep, axis=1)
            vj = jnp.repeat(vj, rep, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kj.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        k_pos = cidx * chunk + jnp.arange(chunk)
        mask = None
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            wm = (q_pos[:, None] - k_pos[None, :]) < window
            mask = wm if mask is None else mask & wm
        if mask is not None:
            s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m_i, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + p.sum(-1, keepdims=True)
        if p_bf16:
            # §Perf: P in bf16 halves the attention matmul's HBM reads; the
            # P@V product still accumulates in fp32 on the MXU
            pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(jnp.bfloat16),
                            vj.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bhqk,bhkd->bhqd", p, vj.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
        o_new = o_i * alpha + pv
        return (m_new, l_new, o_new)

    # python (unrolled) chunk loop: per-chunk rematerialized bodies; unrolled
    # (rather than lax.scan) so per-chunk compute is visible to HLO cost
    # analysis (while bodies are counted once regardless of trip count) and so
    # fully-masked chunks can be skipped statically (causal/sliding-window).
    m_i = jnp.full((b, h, sq, 1), -1e30, jnp.float32)
    l_i = jnp.zeros((b, h, sq, 1), jnp.float32)
    o_i = jnp.zeros((b, h, sq, hd), jnp.float32)
    carry = (m_i, l_i, o_i)
    q_lo = int(q_offset) if isinstance(q_offset, int) else None
    for ci in range(nc):
        if q_lo is not None:
            k_lo, k_hi = ci * chunk, (ci + 1) * chunk - 1
            if causal and k_lo > q_lo + sq - 1:
                continue  # chunk entirely in the future
            if window is not None and (q_lo - k_hi) >= window:
                continue  # chunk entirely outside the window
        carry = body(carry, kc[:, :, ci], vc[:, :, ci], ci)
    m_f, l_f, o_f = carry
    return (o_f / jnp.maximum(l_f, 1e-30)).astype(q.dtype)


def seam_proj(params, cfg):
    """(glue, w) pair for fusing an upstream RS into THIS layer's qkv AG.

    ``glue`` maps the upstream residual output to this layer's AG input (the
    pre-attention rms_norm); ``w`` is the fused qkv per-shard weight — the
    same concat :func:`_project_qkv` uses.  Bias stays local in the consumer.
    """
    w = jnp.concatenate([params["wq"], params["wkv"]], axis=1)
    return (lambda y: rms_norm(y, params["ln"], cfg.norm_eps)), w


def _project_qkv(params, h, pc, lay, hd, qkv=None):
    """Shared AG+GEMM producer for q and kv projections.

    h: [B, s_loc, D] -> q/k/v as [B, S, n, hd] (full gathered sequence).
    ``qkv`` is the already-gathered projection from an upstream fused RS->AG
    seam (pre-bias), skipping the AG+GEMM here."""
    if qkv is None:
        w = jnp.concatenate([params["wq"], params["wkv"]], axis=1)
        qkv = pc.ag_matmul(h, w)  # [B, S, (h_loc + 2*kv_loc)*hd]
    if "bq" in params:
        bias = jnp.concatenate([params["bq"], params["bkv"]])
        qkv = qkv + bias
    b, s_glob = qkv.shape[0], qkv.shape[1]
    qkv = qkv.reshape(b, s_glob, lay.h_loc + 2 * lay.kv_loc, hd)
    q = qkv[:, :, : lay.h_loc]
    k = qkv[:, :, lay.h_loc: lay.h_loc + lay.kv_loc]
    v = qkv[:, :, lay.h_loc + lay.kv_loc:]
    return q, k, v, s_glob


def apply_seq(params, x, pc, cfg, *, causal=True, window=None,
              rope_theta=None, attn_chunk=1024, return_kv=False, qkv=None,
              next_proj=None):
    """Full-sequence attention block body (call inside pc.smap manual region).

    x: [B, s_loc, D] sequence-sharded. Returns [B, s_loc, D] (residual added);
    with ``return_kv``, also the per-shard KV in cache layout
    [B, kv_loc, S, hd] (prefill-into-cache).  The AG+GEMM and GEMM+RS
    collectives take their design point from ``pc``.

    Inter-op seam fusion (``pc.fuse_seams``): ``qkv`` is this layer's fused
    qkv projection already produced by the upstream op's RS->AG ring pass
    (see :func:`seam_proj`); ``next_proj=(glue, w)`` fuses the output-proj RS
    with the next consumer's AG over one shared ring pass, changing the
    return value to ``(y, next_out)`` (with ``return_kv``: ``(y, next_out,
    kv)``).
    """
    lay = _lay(cfg, pc.tp)
    hd = cfg.hd
    b = x.shape[0]
    h = None if qkv is not None else rms_norm(x, params["ln"], cfg.norm_eps)
    q, k, v, s_glob = _project_qkv(params, h, pc, lay, hd, qkv=qkv)

    positions = jnp.arange(s_glob)
    q, k = rope(q, k, positions,
                rope_theta if rope_theta is not None else cfg.rope_theta)
    # [b, S, n, hd] -> [b, n, S, hd]
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)

    o = chunked_attention(q, k, v, causal=causal, window=window,
                          chunk=min(attn_chunk, s_glob), p_bf16=pc.attn_p_bf16)
    o_flat = o.transpose(0, 2, 1, 3).reshape(b, s_glob, lay.h_loc * hd)
    if next_proj is not None:
        glue, w_next = next_proj
        y, nxt = pc.matmul_rs_ag(o_flat, params["wo"], w_next,
                                 residual=x, glue=glue)
        if return_kv:
            return y, nxt, {"k": k, "v": v}
        return y, nxt
    out = pc.matmul_rs(o_flat, params["wo"])  # [B, s_loc, D]
    y = x + out
    if return_kv:
        return y, {"k": k, "v": v}
    return y


def apply_seq_ring(params, x, pc, cfg, *, causal=True, window=None,
                   rope_theta=None, next_proj=None):
    """AG-Q + ring-KV attention block body (paper Fig. 6 layer form).

    Where :func:`apply_seq` gathers the WHOLE qkv projection through the
    AG+GEMM producer and attends on fully-resident KV, this path gathers
    only the (narrow) query projection; K/V project LOCALLY on the sequence
    shard and stay resident while their tiles rotate through
    ``pc.ring_attention`` — the overlapped AG-KV + online-softmax tile plan,
    whose consumer honors the CompSpec tile as (block_q, block_kv).  Every
    rank attends the full query range with its local heads, so the output
    projection is the same GEMM+RS consumer as :func:`apply_seq`.
    x: [B, s_loc, D] -> [B, s_loc, D] (residual added); results match
    :func:`apply_seq` up to fp reassociation.

    MQA (``kv_pad == 1``) rings the one shared head's local projection
    directly.  GQA rings per KV group: every rank gathers the (narrow)
    ``wkv`` columns once, dedupes the GQALayout's replicated copies, and
    projects the FULL distinct-KV width on its sequence shard — the rotating
    tiles then carry every group, and ``pc.ring_attention(kv_select=True)``
    has each rank's online softmax consume only the group its local query
    heads map to.  The extra wire per tile is ``kv_pad``-fold, still far
    below the ``h``-wide AG of :func:`apply_seq`.
    """
    lay = _lay(cfg, pc.tp)
    hd = cfg.hd
    b, s_loc, _ = x.shape
    h = rms_norm(x, params["ln"], cfg.norm_eps)

    q = pc.ag_matmul(h, params["wq"])  # [B, S, h_loc*hd] gathered
    if "bq" in params:
        q = q + params["bq"]
    if lay.kv_pad == 1:
        kv = jnp.einsum("bsd,dn->bsn", h, params["wkv"])  # local shared head
        if "bkv" in params:
            kv = kv + params["bkv"]
        kv = kv.reshape(b, s_loc, 2 * lay.kv_loc, hd)
        k = kv[:, :, : lay.kv_loc]
        v = kv[:, :, lay.kv_loc:]
    else:
        # per-KV-group ring: project all kv_pad distinct groups locally.
        # Per-rank wkv columns pack [K heads (kv_loc*hd) || V heads], so the
        # gather is rank-major: reshape, split k/v, then flatten the
        # (rank, local-head) axes back into the global expanded head order.
        wkv = pc.all_gather_seq(params["wkv"], 1)  # [D, tp * 2*kv_loc*hd]
        wkv = wkv.reshape(cfg.d_model, pc.tp, 2, lay.kv_loc, hd)
        wk = wkv[:, :, 0].reshape(cfg.d_model, lay.kv_store, hd)
        wv = wkv[:, :, 1].reshape(cfg.d_model, lay.kv_store, hd)
        if lay.rep > 1:
            wk = wk[:, :: lay.rep]  # drop the replicated copies
            wv = wv[:, :: lay.rep]
        k = jnp.einsum("bsd,dhe->bshe", h, wk)  # [B, s_loc, kv_pad, hd]
        v = jnp.einsum("bsd,dhe->bshe", h, wv)
        if "bkv" in params:
            bkv = pc.all_gather_seq(params["bkv"], 0)
            bkv = bkv.reshape(pc.tp, 2, lay.kv_loc, hd)
            bk = bkv[:, 0].reshape(lay.kv_store, hd)
            bv = bkv[:, 1].reshape(lay.kv_store, hd)
            if lay.rep > 1:
                bk, bv = bk[:: lay.rep], bv[:: lay.rep]
            k = k + bk
            v = v + bv
    s_glob = q.shape[1]
    q = q.reshape(b, s_glob, lay.h_loc, hd)

    theta = rope_theta if rope_theta is not None else cfg.rope_theta
    q, _ = rope(q, q, jnp.arange(s_glob), theta)
    k_pos = pc.axis_index() * s_loc + jnp.arange(s_loc)  # global KV positions
    _, k = rope(k, k, k_pos, theta)
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)

    o = pc.ring_attention(q, k, v, causal=causal, window=window,
                          kv_select=lay.kv_pad > 1)
    o_flat = o.transpose(0, 2, 1, 3).reshape(b, s_glob, lay.h_loc * hd)
    if next_proj is not None:
        glue, w_next = next_proj
        # fused epilogue: output-proj RS feeds the next consumer's AG
        return pc.matmul_rs_ag(o_flat, params["wo"], w_next,
                               residual=x, glue=glue)
    out = pc.matmul_rs(o_flat, params["wo"])  # [B, s_loc, D]
    return x + out


def apply_cross_seq(params, x, enc, pc, cfg):
    """Cross-attention (enc-dec): queries from x, keys/values from enc.

    x: [B, s_loc, D] (dec seq-sharded), enc: [B, se_loc, D] (enc seq-sharded).
    No rope, non-causal. Inside manual region.
    """
    lay = _lay(cfg, pc.tp)
    hd = cfg.hd
    b = x.shape[0]
    h = rms_norm(x, params["ln"], cfg.norm_eps)

    q = pc.ag_matmul(h, params["wq"])  # [B, Sd, h_loc*hd]
    kv = pc.ag_matmul(enc, params["wkv"])  # [B, Se, kv_loc*2hd]
    if "bq" in params:
        q = q + params["bq"]
        kv = kv + params["bkv"]
    sd, se = q.shape[1], kv.shape[1]
    q = q.reshape(b, sd, lay.h_loc, hd).transpose(0, 2, 1, 3)
    kv = kv.reshape(b, se, 2 * lay.kv_loc, hd)
    k = kv[:, :, : lay.kv_loc].transpose(0, 2, 1, 3)
    v = kv[:, :, lay.kv_loc:].transpose(0, 2, 1, 3)

    o = chunked_attention(q, k, v, causal=False, chunk=min(1024, se))
    o_flat = o.transpose(0, 2, 1, 3).reshape(b, sd, lay.h_loc * hd)
    out = pc.matmul_rs(o_flat, params["wo"])
    return x + out


def build_cross_cache(params, enc, pc, cfg):
    """Precompute cross-attention K/V from the encoder output (decode path).

    enc: [B, se_loc, D] (enc seq-sharded). Returns per-shard k/v
    [B, kv_loc, Se, hd].
    """
    lay = _lay(cfg, pc.tp)
    hd = cfg.hd
    b = enc.shape[0]
    kv = pc.ag_matmul(enc, params["wkv"])
    if "bkv" in params:
        kv = kv + params["bkv"]
    se = kv.shape[1]
    kv = kv.reshape(b, se, 2 * lay.kv_loc, hd)
    k = kv[:, :, : lay.kv_loc].transpose(0, 2, 1, 3)
    v = kv[:, :, lay.kv_loc:].transpose(0, 2, 1, 3)
    return {"k": k, "v": v}


def apply_cross_decode(params, x, cross, pc, cfg):
    """Decode-time cross attention. x: [B, 1, D] replicated; cross: per-shard
    k/v [B, kv_loc, Se, hd]."""
    lay = _lay(cfg, pc.tp)
    hd = cfg.hd
    b = x.shape[0]
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    q = jnp.einsum("bsd,dn->bsn", h, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    qh = q.reshape(b, 1, lay.h_loc, hd).transpose(0, 2, 1, 3)
    rep = lay.h_loc // lay.kv_loc
    kk = jnp.repeat(cross["k"], rep, axis=1) if rep > 1 else cross["k"]
    vv = jnp.repeat(cross["v"], rep, axis=1) if rep > 1 else cross["v"]
    s = jnp.einsum("bhqd,bhkd->bhqk", (qh * hd ** -0.5).astype(jnp.float32),
                   kk.astype(jnp.float32))
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)).astype(x.dtype)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, lay.h_loc * hd)
    out = pc.psum(jnp.einsum("bsn,nd->bsd", o, params["wo"]))
    return x + out


def init_cache(cfg, tp: int, batch: int, max_len: int, dtype=jnp.bfloat16,
               window: Optional[int] = None):
    """Global KV cache arrays (head dim sharded over model).

    Sliding-window layers allocate a *ring buffer* of ``window`` slots instead
    of ``max_len`` — the sub-quadratic memory that makes long-context decode
    (gemma3 long_500k) fit HBM.  Slot ``p % window`` holds position ``p``.
    """
    lay = _lay(cfg, tp)
    length = min(max_len, window) if window is not None else max_len
    shape = (batch, tp * lay.kv_loc, length, cfg.hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def cache_specs(dp):
    return {"k": P(dp, "model", None, None), "v": P(dp, "model", None, None)}


def decode_kernel_takes(c, size, window):
    """Whether :func:`apply_decode` reads a cache of ``size`` rows with the
    decode-attention kernel (``kernels/decode_attention.py``): one query row,
    a cache that is no ring and whose rows fill whole (8, 128) tiles, on the
    TPU target.  Everything else, the emulated target included, takes XLA's
    path."""
    return (c == 1 and not is_ring(size, window) and size % _SPAN == 0
            and not backend.is_emulated())


def decode_read_span(cfg, tp, size, window, dtype):
    """Cache rows per span that a decode pass reads of each slot's cache of
    ``size`` rows for a layer of sliding ``window``, or None where it reads
    every row."""
    if not decode_kernel_takes(1, size, window):
        return None
    lay = _lay(cfg, tp)
    return span_rows(size, lay.kv_loc, cfg.hd, jnp.dtype(dtype).itemsize)


def apply_decode(params, x, cache, cache_len, pc, cfg, *, window=None,
                 rope_theta=None, q_valid=None, layer=None):
    """Chunked decode body (inside manual region).

    x: [B, C, D] replicated over model (C == 1 is plain decode; C > 1 is a
    prefill chunk); cache k/v: [B, kv_loc, S_max, hd] per-shard, or with
    ``layer`` (an int32 index) the stack of all the scan's layers
    [n_units, B, kv_loc, S_max, hd], of which this layer reads ``layer``.
    ``cache_len`` is the number of tokens already in each slot's cache — a
    scalar or a per-slot [B] vector (the continuous-batching engine runs
    heterogeneous lengths).  ``q_valid`` ([B] int, optional) is how many of
    the C chunk rows are real per slot: rows past it leave the cache as it
    is and their outputs are garbage the caller ignores.

    The cache is only read.  Returns (x_out, rows): the chunk's new keys and
    values ``rows["k"]``/``["v"]`` [B, kv_loc, C, hd] and ``rows["slot"]``
    [B, C], the cache row each goes to (``cache_size``, out of bounds, past
    ``q_valid``).  :func:`write_rows` puts them in, so a caller holding many
    layers' caches writes them all at once.

    The chunk attends in two parts — the pre-existing cache rows, then the
    causal in-chunk keys — so the chunk's own k/v never round-trip through a
    ring slot another in-flight query still needs.  Query head ``g*rep + r``
    reads KV head ``g`` where it lies, with no expanded copy of the cache.
    Requires C <= cache size for ring (sliding-window) layers.

    Where :func:`decode_kernel_takes`, part 1 is the decode-attention
    kernel, which reads the stack in place and only the spans of rows that
    are live in slots with a row to decode; otherwise XLA reads the layer's
    whole cache.
    """
    lay = _lay(cfg, pc.tp)
    hd = cfg.hd
    b, c, _ = x.shape
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    nv = (jnp.full((b,), c, jnp.int32) if q_valid is None
          else jnp.asarray(q_valid, jnp.int32))
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    w = jnp.concatenate([params["wq"], params["wkv"]], axis=1)
    qkv = jnp.einsum("bsd,dn->bsn", h, w)
    if "bq" in params:
        qkv = qkv + jnp.concatenate([params["bq"], params["bkv"]])
    qkv = qkv.reshape(b, c, lay.h_loc + 2 * lay.kv_loc, hd)
    q = qkv[:, :, : lay.h_loc]
    k = qkv[:, :, lay.h_loc: lay.h_loc + lay.kv_loc]
    v = qkv[:, :, lay.h_loc + lay.kv_loc:]

    pos = lens[:, None] + jnp.arange(c)[None, :]  # [B, C] global positions
    theta = rope_theta if rope_theta is not None else cfg.rope_theta
    q, k = rope(q, k, pos, theta)

    cache_size = cache["k"].shape[-2]
    ring = is_ring(cache_size, window)
    if ring and c > cache_size:
        raise ValueError(
            f"decode chunk C={c} exceeds ring cache size {cache_size}; "
            "chunked prefill must keep chunks within the sliding window")
    # each row's target: rows past q_valid target ``cache_size``, out of
    # bounds, which write_rows drops
    slots = jnp.remainder(pos, cache_size) if ring else pos
    slots = jnp.where(jnp.arange(c)[None, :] < nv[:, None], slots, cache_size)
    rows = {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3),
            "slot": slots}

    g, rep = lay.kv_loc, lay.h_loc // lay.kv_loc
    qh = q.transpose(0, 2, 1, 3)  # [b, h_loc, C, hd]
    qf = (qh * hd ** -0.5).astype(jnp.float32).reshape(b, g, rep, c, hd)

    def project(o):  # [b, g, rep, C, hd] f32 -> the block's output and rows
        o = o.reshape(b, lay.h_loc, c, hd).astype(x.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(b, c, lay.h_loc * hd)
        return x + pc.psum(jnp.einsum("bsn,nd->bsd", o, params["wo"])), rows

    if decode_kernel_takes(c, cache_size, window):
        return project(_decode_one_row(qf[:, :, :, 0], k[:, 0], v[:, 0], cache, layer,
                                       lens, nv, window, pc)[:, :, :, None])
    if layer is not None:
        cache = {n: lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                 for n, a in cache.items()}
    # part 1: the pre-existing cache rows (the chunk is not in them yet).
    # One query row per head is a multiply and sum in f32 that reads the
    # cache where it lies; as a product on the MXU, XLA would convert the
    # whole stacked cache to bf16 ahead of the layer scan on every pass.
    one_row = c == 1
    # the cache as the products take it; for one row, broadcast here (in
    # the fused product, not in memory) so that XLA's staging of each layer's
    # K and V, which it names after the broadcast, is named as a cache read
    with jax.named_scope("kv_read"):
        kk = cache["k"].astype(jnp.float32)  # [b, g, L, hd]
        vv = cache["v"].astype(jnp.float32)
        if one_row:  # against [b, g, rep, 1, (L,) hd]
            kk, vv = (jnp.broadcast_to(a[:, :, None, None], (b, g, rep, 1) + a.shape[2:])
                      for a in (kk, vv))
    if one_row:
        s1 = jnp.sum(qf[..., None, :] * kk, axis=-1)
    else:
        s1 = jnp.einsum("bgrqd,bgkd->bgrqk", qf, kk)
    j = jnp.arange(cache_size)
    if ring:
        # slot j last held position p_j = last - ((last - j) mod size)
        last = lens - 1
        p_j = last[:, None] - jnp.remainder(last[:, None] - j[None, :],
                                            cache_size)  # [B, L]
        m1 = (p_j >= 0)[:, None, :] & ((pos[:, :, None] - p_j[:, None, :])
                                       < window)  # [B, C, L]
    else:
        m1 = jnp.broadcast_to((j[None, :] < lens[:, None])[:, None, :],
                              (b, c, cache_size))
        if window is not None:
            m1 = m1 & ((pos[:, :, None] - j[None, None, :]) < window)
    s1 = jnp.where(m1[:, None, None], s1, -1e30)
    # part 2: causal in-chunk keys (row i attends rows <= i, valid only)
    s2 = jnp.einsum("bgrqd,bkgd->bgrqk", qf, k.astype(jnp.float32))
    qi = jnp.arange(c)
    m2 = (qi[None, :, None] >= qi[None, None, :]) & \
        (qi[None, None, :] < nv[:, None, None])  # [B, C, C]
    if window is not None:
        m2 = m2 & ((qi[None, :, None] - qi[None, None, :]) < window)
    s2 = jnp.where(m2[:, None, None], s2, -1e30)

    p = jax.nn.softmax(jnp.concatenate([s1, s2], axis=-1), axis=-1)
    if one_row:
        o = jnp.sum(p[..., :cache_size, None] * vv, axis=-2)
    else:
        o = jnp.einsum("bgrqk,bgkd->bgrqd", p[..., :cache_size], vv)
    o = o + jnp.einsum("bgrqk,bkgd->bgrqd", p[..., cache_size:],
                       v.astype(jnp.float32))
    return project(o)


def _decode_one_row(q, k, v, cache, layer, lens, nv, window, pc):
    """:func:`apply_decode`'s attention for one query row a head, part 1 by
    the decode-attention kernel, merged with part 2, the row's own key.

    q: [B, g, rep, hd] f32, scaled; k, v: the row's own [B, g, hd].
    Returns [B, g, rep, hd] f32."""
    if layer is None:  # one layer's cache: a stack of one
        cache, layer = {n: a[None] for n, a in cache.items()}, 0
    live = nv > 0
    lo = jnp.zeros_like(lens) if window is None else jnp.maximum(lens - window + 1, 0)
    # a kernel is not partitioned automatically: each shard of the
    # data-parallel axes reads the slots it holds (all, where they do not
    # divide the slots)
    dp = pc.dp_spec() if q.shape[0] % pc.dp == 0 else None
    auto = set(pc.mesh.axis_names) - {pc.axis}
    # Pallas's plain interpreter on the emulated target: the TPU
    # interpreter's callbacks cannot run under the partially automatic
    # shard_map of the serving step
    read = partial(decode_attention, interpret="hlo")
    read = read if not auto else shard_map(
        read, jax.sharding.get_abstract_mesh(),
        in_specs=(P(dp), P(None, dp), P(None, dp), P(), P(dp), P(dp)),
        out_specs=P(dp), axis_names=auto)
    with jax.named_scope("kv_read"):
        # the cache as its swapped view [.., hd, S]: the layout it has
        acc, m1, l1 = read(q, jnp.swapaxes(cache["k"], -1, -2),
                           jnp.swapaxes(cache["v"], -1, -2), jnp.asarray(layer, jnp.int32),
                           lo, jnp.where(live, lens, 0))
    # part 2: the row's own key, which it always attends where it is valid
    s2 = jnp.sum(q * k.astype(jnp.float32)[:, :, None], axis=-1, keepdims=True)
    s2 = jnp.where(live[:, None, None, None], s2, NEG_INF)
    m = jnp.maximum(m1, s2)
    a1, a2 = jnp.exp(m1 - m), jnp.exp(s2 - m)
    return (acc * a1 + a2 * v.astype(jnp.float32)[:, :, None]) / (l1 * a1 + a2)


def is_ring(size, window):
    """Whether a cache of ``size`` rows for a layer of sliding ``window`` is a
    ring, row ``p % size`` holding position ``p`` (:func:`init_cache`)."""
    return window is not None and size <= window


def rows_specs(dp):
    """Shard specs of :func:`apply_decode`'s rows: k/v as the cache's."""
    return {**cache_specs(dp), "slot": P(dp, None)}


def write_rows(cache, rows, pc, window=None):
    """Put :func:`apply_decode`'s rows into ``cache``, in place where the
    caller donates it; rows whose ``slot`` is out of bounds are dropped.
    ``window``: the layer's sliding window, whose ring (:func:`is_ring`) a
    chunk can wrap past the end of.  Any axes before the slot axis (a stack
    of layers, whose rows share their slots) are written at once.  Each
    shard of ``pc``'s mesh writes the slots it holds: left to the automatic
    partitioner, a per-slot update of a cache sharded over slots gathers the
    whole cache.  (A slot count that the data-parallel shards do not divide
    is written whole on each.)"""
    lead = cache["k"].ndim - 4
    dp = pc.dp_spec() if cache["k"].shape[lead] % pc.dp == 0 else None

    def stacked(specs):
        return {n: P(*((None,) * lead + tuple(s))) for n, s in specs.items()}

    cs = stacked(cache_specs(dp))
    ring = is_ring(cache["k"].shape[-2], window)
    return shard_map(partial(_write_rows, ring=ring), pc.mesh,
                     in_specs=(cs, stacked(rows_specs(dp))), out_specs=cs)(cache, rows)


def _write_rows(cache, rows, ring):
    """:func:`write_rows` on one shard.

    The TPU keeps an f32 cache of head dim 64 with ``max_len`` minor, so a
    row is one lane of many (8, 128) tiles.  Each slot's rows are written by
    reading the span of cache rows that holds them — from a multiple of
    ``_SPAN``, wide enough for C rows from any start, and for a chunk that
    wraps past a ring's end the span at row 0 too — putting the rows in, and
    writing the span back whole.  The span is written per slot, unrolled: a
    scatter, a gather of the rows, or a loop over slots makes XLA lay the
    cache out with its head dim minor, padded, and copy it to and from the
    layout that attention reads."""
    slot = rows["slot"].reshape((-1,) + rows["slot"].shape[-2:])[0]  # [B, C]
    n_slots, c = slot.shape
    buf = cache["k"]
    lead, size = buf.ndim - 4, buf.shape[-2]
    width = min(size, -(-(c + _SPAN - 1) // _SPAN) * _SPAN)
    window = buf.shape[:lead] + (1, buf.shape[-3], width, buf.shape[-1])
    first = jnp.minimum(slot[:, 0], size - 1)  # a slot with no kept row: its last span
    starts = [jnp.minimum(first // _SPAN * _SPAN, size - width)]
    if ring and c > 1 and size > width:
        starts.append(jnp.zeros_like(first))
    starts = jnp.stack(starts, 1)  # [B, spans]
    hits = (slot[:, None, :, None] == starts[:, :, None, None] + jnp.arange(width)).any(2)
    # chunk row t lands on span row slot0 + t - start, and on a ring also on
    # slot0 + t - size - start (past its end).  Each placement goes into the
    # span padded by C rows on each side, at an offset clipped so that one
    # that misses the span lies wholly in the padding.
    shifts = jnp.asarray([0, -size] if ring else [0])
    offsets = jnp.clip(slot[:, :1, None] - starts[:, :, None] + shifts + c, 0, width + c)
    out = dict(cache)
    with jax.named_scope("kv_write"):
        for i in range(n_slots):
            for e in range(starts.shape[1]):
                corner = (0,) * lead + (i, 0, starts[i, e], 0)
                for name in ("k", "v"):
                    span = lax.dynamic_slice(out[name], corner, window)
                    new = lax.index_in_dim(rows[name], i, lead).astype(span.dtype)
                    if c > 1:
                        pad = jnp.zeros(window[:-2] + (width + 2 * c, window[-1]),
                                        span.dtype)
                        for off in offsets[i, e]:
                            pad = lax.dynamic_update_slice_in_dim(pad, new, off,
                                                                  axis=lead + 2)
                        new = lax.slice_in_dim(pad, c, c + width, axis=lead + 2)
                    span = jnp.where(hits[i, e][:, None], new, span)
                    out[name] = lax.dynamic_update_slice(out[name], span, corner)
    return out
