"""Encoder-decoder LM (seamless-m4t backbone).

Encoder: non-causal attention + FFN over stub frame embeddings (scanned).
Decoder: causal self-attention + cross-attention + FFN (scanned).
The paper's technique covers every projection (AG+GEMM / GEMM+RS) on both
stacks and the cross-attention KV gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn import attention, ffn
from repro.nn.layers import emb_init, rms_norm
from repro.parallel.context import ParallelContext

__all__ = ["init", "specs", "forward", "init_caches", "cache_specs",
           "decode_step", "encode", "grad_masks", "sync_grads"]


def _enc_layer_init(key, cfg, pc, dtype):
    k1, k2 = jax.random.split(key)
    return {"attn": attention.init(k1, cfg, pc.tp, dtype),
            "ffn": ffn.init(k2, cfg, pc.tp, dtype)}


def _dec_layer_init(key, cfg, pc, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"attn": attention.init(k1, cfg, pc.tp, dtype),
            "cross": attention.init(k2, cfg, pc.tp, dtype),
            "ffn": ffn.init(k3, cfg, pc.tp, dtype)}


def _enc_layer_specs(cfg, pc):
    dp = pc.dp_spec()
    return {"attn": attention.specs(cfg, pc.tp, dp),
            "ffn": ffn.specs(cfg, pc.tp, dp)}


def _dec_layer_specs(cfg, pc):
    dp = pc.dp_spec()
    return {"attn": attention.specs(cfg, pc.tp, dp),
            "cross": attention.specs(cfg, pc.tp, dp),
            "ffn": ffn.specs(cfg, pc.tp, dp)}


def init(key, cfg, pc: ParallelContext, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 6)
    n_enc, n_dec = cfg.encoder_layers, cfg.n_layers

    def stack(k, n, f):
        return jax.vmap(lambda kk: f(kk, cfg, pc, dtype))(jax.random.split(k, n))

    from repro.models.lm import padded_vocab

    v_pad = padded_vocab(cfg, pc)
    return {
        "embed": emb_init(ks[0], (v_pad, cfg.d_model), dtype),
        "enc_scan": stack(ks[1], n_enc, _enc_layer_init),
        "enc_ln": jnp.zeros((cfg.d_model,), dtype),
        "dec_scan": stack(ks[2], n_dec, _dec_layer_init),
        "final_ln": jnp.zeros((cfg.d_model,), dtype),
        "lm_head": emb_init(ks[3], (cfg.d_model, v_pad), dtype),
    }


def _stackP(tree):
    return jax.tree_util.tree_map(lambda sp: P(*((None,) + tuple(sp))), tree,
                                  is_leaf=lambda v: isinstance(v, P))


def specs(cfg, pc: ParallelContext):
    dp = pc.dp_spec()
    return {
        "embed": P("model", dp),
        "enc_scan": _stackP(_enc_layer_specs(cfg, pc)),
        "enc_ln": P(None),
        "dec_scan": _stackP(_dec_layer_specs(cfg, pc)),
        "final_ln": P(None),
        "lm_head": P(dp, "model"),
    }


def sync_grads(grads, cfg, pc: ParallelContext):
    """Average the expanded kv-weight replica gradients (GQA with kv < tp).

    kv weights are stored with ``rep`` identical copies (nn/layers.GQALayout);
    their per-copy gradients differ (different q-head groups), so they are
    group-averaged here to keep the copies identical — Megatron-style GQA
    replication semantics.  No-op when rep == 1.  Works on any pytree whose
    attention param dicts contain a "wkv" leaf (stacked or not).
    """
    from repro.nn.layers import gqa_layout, sync_kv_grad

    if not cfg.n_heads:
        return grads
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, pc.tp)
    if lay.rep == 1:
        return grads

    def walk(node):
        if isinstance(node, dict):
            if "wkv" in node:
                node = dict(node)
                node["wkv"] = sync_kv_grad(node["wkv"], lay, axis=-1)
                if "bkv" in node:
                    node["bkv"] = sync_kv_grad(node["bkv"], lay, axis=-1)
                return node
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, tuple):
            return tuple(walk(v) for v in node)
        return node

    return walk(grads)


def grad_masks(cfg, pc: ParallelContext):
    return jax.tree_util.tree_map(lambda _: None, specs(cfg, pc),
                                  is_leaf=lambda v: isinstance(v, P))


def _smap_attn(pc, cfg, p, x, *, causal, fn=attention.apply_seq, extra=()):
    full = attention.specs(cfg, pc.tp, pc.dp_spec())
    sp = {k: pc.manual(v) for k, v in full.items()}
    xs = P(None, "model", None)
    p = pc.use_gather(p, full)
    if extra:
        return pc.smap(
            lambda p_, x_, e_: attention.apply_cross_seq(p_, x_, e_, pc, cfg),
            in_specs=(sp, xs, xs), out_specs=xs)(p, x, *extra)
    return pc.smap(
        lambda p_, x_: attention.apply_seq(p_, x_, pc, cfg, causal=causal),
        in_specs=(sp, xs), out_specs=xs)(p, x)


def _smap_ffn(pc, cfg, p, x):
    full = ffn.specs(cfg, pc.tp, pc.dp_spec())
    sp = {k: pc.manual(v) for k, v in full.items()}
    xs = P(None, "model", None)
    return pc.smap(lambda p_, x_: ffn.apply_seq(p_, x_, pc, cfg),
                   in_specs=(sp, xs), out_specs=xs)(pc.use_gather(p, full), x)


def encode(params, cfg, pc, enc_embeds, remat_policy="none", unroll=False):
    """enc_embeds: [B, S_enc, D] stub frame embeddings -> [B, S_enc, D]."""
    x = enc_embeds
    x = jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(pc.mesh, P(pc.dp_spec(), "model", None)))

    def body(h, lp):
        h = _smap_attn(pc, cfg, lp["attn"], h, causal=False)
        h = _smap_ffn(pc, cfg, lp["ffn"], h)
        return h, None

    b = jax.checkpoint(body) if remat_policy != "none" else body
    if unroll:
        for u in range(cfg.encoder_layers):
            x, _ = b(x, jax.tree_util.tree_map(lambda a: a[u], params["enc_scan"]))
    else:
        x, _ = jax.lax.scan(b, x, params["enc_scan"])
    return rms_norm(x, params["enc_ln"], cfg.norm_eps)


def forward(params, cfg, pc: ParallelContext, tokens, embeds=None,
            remat_policy: str = "none", unroll: bool = False):
    """tokens: decoder input ids [B, S_dec]; embeds: encoder frames [B,S_enc,D].

    Returns (logits, aux=0)."""
    enc = encode(params, cfg, pc, embeds, remat_policy, unroll=unroll)
    x = jnp.take(params["embed"], tokens, axis=0)
    x = jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(pc.mesh, P(pc.dp_spec(), "model", None)))

    def body(h, lp):
        h = _smap_attn(pc, cfg, lp["attn"], h, causal=True)
        h = _smap_attn(pc, cfg, lp["cross"], h, causal=False, extra=(enc,))
        h = _smap_ffn(pc, cfg, lp["ffn"], h)
        return h, None

    b = jax.checkpoint(body) if remat_policy != "none" else body
    if unroll:
        for u in range(cfg.n_layers):
            x, _ = b(x, jax.tree_util.tree_map(lambda a: a[u], params["dec_scan"]))
    else:
        x, _ = jax.lax.scan(b, x, params["dec_scan"])
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = jax.lax.with_sharding_constraint(
        params["lm_head"], jax.sharding.NamedSharding(pc.mesh, P(None, "model")))
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    return logits[..., : cfg.vocab_size], jnp.zeros((), jnp.float32)


# ---- decode -----------------------------------------------------------------

def init_caches(cfg, pc, batch, max_len, dtype=jnp.bfloat16):
    n_dec = cfg.n_layers
    self_c = attention.init_cache(cfg, pc.tp, batch, max_len, dtype)
    lay = attention.gqa_layout(cfg.n_heads, cfg.n_kv_heads, pc.tp)
    cross_c = {
        "k": jnp.zeros((batch, pc.tp * lay.kv_loc, cfg.enc_len, cfg.hd), dtype),
        "v": jnp.zeros((batch, pc.tp * lay.kv_loc, cfg.enc_len, cfg.hd), dtype),
    }
    def stack(c):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_dec,) + a.shape).copy(), c)
    return {"self": stack(self_c), "cross": stack(cross_c)}


def cache_specs(cfg, pc):
    dp = pc.dp_spec()
    sp = _stackP(attention.cache_specs(dp))
    return {"self": sp, "cross": sp}


def build_cross_caches(params, cfg, pc, enc):
    """Precompute per-layer cross K/V from the encoder output."""
    sp = {k: pc.manual(v) for k, v in
          attention.specs(cfg, pc.tp, pc.dp_spec()).items()}
    xs = P(None, "model", None)
    cs = {k: pc.manual(v) for k, v in attention.cache_specs(pc.dp_spec()).items()}

    full = attention.specs(cfg, pc.tp, pc.dp_spec())

    def per_layer(lp):
        return pc.smap(
            lambda p_, e_: attention.build_cross_cache(p_, e_, pc, cfg),
            in_specs=(sp, xs), out_specs=cs)(pc.use_gather(lp["cross"], full), enc)

    return jax.lax.map(per_layer, params["dec_scan"])


def decode_step(params, caches, cfg, pc: ParallelContext, tokens, cache_len,
                unroll: bool = False):
    """One decoder step with precomputed cross caches."""
    x = jnp.take(params["embed"], tokens, axis=0)
    dp = pc.dp_spec()
    asp = {k: pc.manual(v) for k, v in
           attention.specs(cfg, pc.tp, dp).items()}
    csp = {k: pc.manual(v) for k, v in attention.cache_specs(dp).items()}
    rsp = {k: pc.manual(v) for k, v in attention.rows_specs(dp).items()}
    xr = P(None, None, None)

    afull = attention.specs(cfg, pc.tp, dp)
    ffull = ffn.specs(cfg, pc.tp, dp)

    def body(h, xs_):
        lp, self_c, cross_c = xs_
        lp = {"attn": pc.use_gather(lp["attn"], afull),
              "cross": pc.use_gather(lp["cross"], afull),
              "ffn": pc.use_gather(lp["ffn"], ffull)}
        h, rows = pc.smap(
            lambda p_, x_, c_, n_: attention.apply_decode(p_, x_, c_, n_, pc, cfg),
            in_specs=(asp, xr, csp, P()), out_specs=(xr, rsp),
        )(lp["attn"], h, self_c, cache_len)
        h = pc.smap(
            lambda p_, x_, c_: attention.apply_cross_decode(p_, x_, c_, pc, cfg),
            in_specs=(asp, xr, csp), out_specs=xr,
        )(lp["cross"], h, cross_c)
        fsp = {k: pc.manual(v) for k, v in ffn.specs(cfg, pc.tp, dp).items()}
        h = pc.smap(lambda p_, x_: ffn.apply_decode(p_, x_, pc, cfg),
                    in_specs=(fsp, xr), out_specs=xr)(lp["ffn"], h)
        return h, rows

    if unroll:
        collected = []
        for u in range(cfg.n_layers):
            def sl(t, _u=u):
                return jax.tree_util.tree_map(lambda a: a[_u], t)
            x, rows = body(x, (sl(params["dec_scan"]), sl(caches["self"]),
                               sl(caches["cross"])))
            collected.append(rows)
        rows = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *collected)
    else:
        x, rows = jax.lax.scan(
            body, x, (params["dec_scan"], caches["self"], caches["cross"]))
    new_self = attention.write_rows(caches["self"], rows, pc)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = jax.lax.with_sharding_constraint(
        params["lm_head"], jax.sharding.NamedSharding(pc.mesh, P(None, "model")))
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    return logits[..., : cfg.vocab_size], {"self": new_self,
                                           "cross": caches["cross"]}
