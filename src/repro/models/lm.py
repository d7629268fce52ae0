"""Decoder-only LM covering dense / MoE / SSM / hybrid / VLM architectures.

Depth is organized as repeated *pattern units* (cfg.pattern), scanned with
stacked parameters for O(1) HLO size at any depth; non-pattern layers
(DeepSeek's leading dense layers, depth remainders) are unrolled.  Each layer
kind wraps its body in a partial-auto shard_map (manual over the TP axis) —
see nn/* for the per-kind bodies.

Layer kinds: "attn" (global attention + FFN), "attn_local" (sliding window),
"attn_dense" (attention + dense MLP in an otherwise-MoE model), "mamba"
(SSD mixer, no FFN), "shared_attn" (attention + FFN with parameters shared
across all occurrences — Zamba2).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn import attention, ffn, moe, mamba
from repro.nn.layers import emb_init
from repro.parallel.context import ParallelContext

__all__ = ["init", "specs", "forward", "init_caches", "cache_specs",
           "decode_step", "grad_masks", "sync_grads", "layer_plan", "LayerDef"]

# per-shard spec of a fused seam's gathered qkv projection ([B, S, cols_loc],
# column-sharded over the TP axis) as it crosses between layer smap regions
_SEAM_QKV_SPEC = P(None, None, "model")


@dataclasses.dataclass(frozen=True)
class LayerDef:
    kind: str  # attn | attn_local | attn_dense | mamba | shared_attn
    ffn_kind: Optional[str]  # mlp | moe | None
    window: Optional[int]
    theta: float
    shared: bool = False  # parameters shared across occurrences (zamba2)

    @property
    def mixer_scope(self) -> str:
        """Name of the device scope around this layer's mixer."""
        return "mamba" if self.kind == "mamba" else "attn"

    # ---- params ---------------------------------------------------------------
    def init(self, key, cfg, pc, dtype):
        ks = jax.random.split(key, 2)
        p = {}
        if self.kind == "mamba":
            p["mixer"] = mamba.init(ks[0], cfg, pc.tp, dtype)
        elif not self.shared:
            p["mixer"] = attention.init(ks[0], cfg, pc.tp, dtype)
        if self.ffn_kind == "mlp":
            d_ff = cfg.moe.dense_d_ff if self.kind == "attn_dense" and cfg.moe else cfg.d_ff
            p["ffn"] = ffn.init(ks[1], cfg, pc.tp, dtype, d_ff=d_ff)
        elif self.ffn_kind == "moe":
            p["ffn"] = moe.init(ks[1], cfg, pc.tp, dtype)
        return p

    def specs(self, cfg, pc):
        dp = pc.dp_spec()
        s = {}
        if self.kind == "mamba":
            s["mixer"] = mamba.specs(cfg, pc.tp, dp)
        elif not self.shared:
            s["mixer"] = attention.specs(cfg, pc.tp, dp)
        if self.ffn_kind == "mlp":
            s["ffn"] = ffn.specs(cfg, pc.tp, dp)
        elif self.ffn_kind == "moe":
            s["ffn"] = moe.specs(cfg, pc.tp, dp)
        return s

    def grad_masks(self, cfg, pc):
        m = jax.tree_util.tree_map(lambda _: None, self.specs(cfg, pc))
        if self.kind != "mamba" and not self.shared:
            am = attention.grad_masks(cfg, pc.tp)
            if am is not None:
                m["mixer"] = am
        return m

    # ---- seq (train / prefill) --------------------------------------------------
    def apply_seq(self, params, x, pc, cfg, shared_params=None):
        """x: [B, s_loc, D] (seq-sharded). Returns (x, aux_loss)."""
        mixer_params = shared_params if self.shared else params["mixer"]
        aux = jnp.zeros((), jnp.float32)

        with jax.named_scope(self.mixer_scope):
            if self.kind == "mamba":
                full = mamba.specs(cfg, pc.tp, pc.dp_spec())
                sp = {k: pc.manual(v) for k, v in full.items()}
                x = pc.smap(
                    lambda p_, x_: mamba.apply_seq(p_, x_, pc, cfg),
                    in_specs=(sp, P(None, "model", None)),
                    out_specs=P(None, "model", None),
                )(pc.use_gather(mixer_params, full), x)
            else:
                full = attention.specs(cfg, pc.tp, pc.dp_spec())
                sp = {k: pc.manual(v) for k, v in full.items()}
                x = pc.smap(
                    lambda p_, x_: attention.apply_seq(
                        p_, x_, pc, cfg, causal=True, window=self.window,
                        rope_theta=self.theta),
                    in_specs=(sp, P(None, "model", None)),
                    out_specs=P(None, "model", None),
                )(pc.use_gather(mixer_params, full), x)

        if self.ffn_kind == "mlp":
            full = ffn.specs(cfg, pc.tp, pc.dp_spec())
            sp = {k: pc.manual(v) for k, v in full.items()}
            with jax.named_scope("mlp"):
                x = pc.smap(
                    lambda p_, x_: ffn.apply_seq(p_, x_, pc, cfg),
                    in_specs=(sp, P(None, "model", None)),
                    out_specs=P(None, "model", None),
                )(pc.use_gather(params["ffn"], full), x)
        elif self.ffn_kind == "moe":
            full = moe.specs(cfg, pc.tp, pc.dp_spec())
            sp = jax.tree_util.tree_map(
                pc.manual, full, is_leaf=lambda v: isinstance(v, P))
            with jax.named_scope("moe"):
                x, aux = pc.smap(
                    lambda p_, x_: moe.apply_seq(p_, x_, pc, cfg),
                    in_specs=(sp, P(None, "model", None)),
                    out_specs=(P(None, "model", None), P()),
                )(pc.use_gather(params["ffn"], full), x)
        return x, aux

    # ---- fused RS->AG seams (pc.fuse_seams) -----------------------------------
    def seam_eligible(self) -> bool:
        """Layer can join a fused RS->AG seam chain: attention + dense MLP.

        Mamba has no RS epilogue feeding an AG consumer; MoE's gather is the
        ag_moe flow, not a plain ag_matmul — both break the chain.
        """
        return self.kind != "mamba" and self.ffn_kind == "mlp"

    def apply_seq_fused(self, params, x, pc, cfg, shared_params=None,
                        qkv=None, next_mixer=None):
        """Seam-fused layer body: ONE smap region for attention + MLP.

        The attention output-proj RS feeds the MLP gate/up AG over one shared
        ring pass (intra-layer seam); with ``next_mixer`` (the next layer's
        attention params) the MLP down-proj RS additionally produces the NEXT
        layer's qkv projection (inter-layer seam), returned as ``next_qkv``
        so the caller threads it into the next ``apply_seq_fused``.  ``qkv``
        is this layer's projection from the previous layer's seam.
        Returns (x, aux_loss, next_qkv).
        """
        mixer_params = shared_params if self.shared else params["mixer"]
        afull = attention.specs(cfg, pc.tp, pc.dp_spec())
        asp = {k: pc.manual(v) for k, v in afull.items()}
        ffull = ffn.specs(cfg, pc.tp, pc.dp_spec())
        fsp = {k: pc.manual(v) for k, v in ffull.items()}
        aux = jnp.zeros((), jnp.float32)

        args = [pc.use_gather(mixer_params, afull),
                pc.use_gather(params["ffn"], ffull), x]
        in_specs = [asp, fsp, P(None, "model", None)]
        if qkv is not None:
            args.append(qkv)
            in_specs.append(_SEAM_QKV_SPEC)
        if next_mixer is not None:
            args.append(pc.use_gather(next_mixer, afull))
            in_specs.append(asp)

        def body(mp_, fp_, x_, *rest):
            it = iter(rest)
            qkv_ = next(it) if qkv is not None else None
            np_ = next(it) if next_mixer is not None else None
            with jax.named_scope("attn"):
                y, gu = attention.apply_seq(
                    mp_, x_, pc, cfg, causal=True, window=self.window,
                    rope_theta=self.theta, qkv=qkv_,
                    next_proj=ffn.seam_proj(fp_, cfg))
            with jax.named_scope("mlp"):
                if np_ is None:
                    return ffn.apply_seq(fp_, y, pc, cfg, gu=gu)
                return ffn.apply_seq(fp_, y, pc, cfg, gu=gu,
                                     next_proj=attention.seam_proj(np_, cfg))

        if next_mixer is not None:
            x, nqkv = pc.smap(
                body, in_specs=tuple(in_specs),
                out_specs=(P(None, "model", None), _SEAM_QKV_SPEC))(*args)
            return x, aux, nqkv
        x = pc.smap(body, in_specs=tuple(in_specs),
                    out_specs=P(None, "model", None))(*args)
        return x, aux, None

    # ---- prefill (fills decode caches while computing logits) -----------------
    def apply_prefill(self, params, x, pc, cfg, max_len, shared_params=None):
        """Like apply_seq, but also returns this layer's decode cache with the
        sequence dimension padded to ``max_len``."""
        mixer_params = shared_params if self.shared else params["mixer"]
        aux = jnp.zeros((), jnp.float32)

        with jax.named_scope(self.mixer_scope):
            if self.kind == "mamba":
                full = mamba.specs(cfg, pc.tp, pc.dp_spec())
                sp = {k: pc.manual(v) for k, v in full.items()}
                cs = {k: pc.manual(v) for k, v in mamba.cache_specs(pc.dp_spec()).items()}
                x, cache = pc.smap(
                    lambda p_, x_: mamba.apply_seq(p_, x_, pc, cfg, return_state=True),
                    in_specs=(sp, P(None, "model", None)),
                    out_specs=(P(None, "model", None), cs),
                )(pc.use_gather(mixer_params, full), x)
            else:
                full = attention.specs(cfg, pc.tp, pc.dp_spec())
                sp = {k: pc.manual(v) for k, v in full.items()}
                cs = {k: pc.manual(v) for k, v in
                      attention.cache_specs(pc.dp_spec()).items()}

                def fn(p_, x_):
                    y, kv = attention.apply_seq(
                        p_, x_, pc, cfg, causal=True, window=self.window,
                        rope_theta=self.theta, return_kv=True)
                    s_len = kv["k"].shape[2]
                    if self.window is not None and self.window < max_len:
                        # ring-buffer layout: slot p % window holds position p
                        w = self.window
                        if s_len >= w:
                            kv = {n: jnp.roll(a[:, :, s_len - w:], s_len % w, axis=2)
                                  for n, a in kv.items()}
                        else:
                            kv = {n: jnp.pad(a, ((0, 0), (0, 0), (0, w - s_len), (0, 0)))
                                  for n, a in kv.items()}
                    else:
                        pad = max_len - s_len
                        kv = {n: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                              for n, a in kv.items()}
                    return y, kv

                x, cache = pc.smap(
                    fn, in_specs=(sp, P(None, "model", None)),
                    out_specs=(P(None, "model", None), cs),
                )(pc.use_gather(mixer_params, full), x)

        if self.ffn_kind == "mlp":
            full = ffn.specs(cfg, pc.tp, pc.dp_spec())
            sp = {k: pc.manual(v) for k, v in full.items()}
            with jax.named_scope("mlp"):
                x = pc.smap(
                    lambda p_, x_: ffn.apply_seq(p_, x_, pc, cfg),
                    in_specs=(sp, P(None, "model", None)),
                    out_specs=P(None, "model", None),
                )(pc.use_gather(params["ffn"], full), x)
        elif self.ffn_kind == "moe":
            full = moe.specs(cfg, pc.tp, pc.dp_spec())
            sp = jax.tree_util.tree_map(
                pc.manual, full, is_leaf=lambda v: isinstance(v, P))
            with jax.named_scope("moe"):
                x, aux = pc.smap(
                    lambda p_, x_: moe.apply_seq(p_, x_, pc, cfg),
                    in_specs=(sp, P(None, "model", None)),
                    out_specs=(P(None, "model", None), P()),
                )(pc.use_gather(params["ffn"], full), x)
        return x, aux, cache

    # ---- decode -----------------------------------------------------------------
    def init_cache(self, cfg, pc, batch, max_len, dtype):
        if self.kind == "mamba":
            return mamba.init_cache(cfg, pc.tp, batch, dtype)
        return attention.init_cache(cfg, pc.tp, batch, max_len, dtype,
                                    window=self.window)

    def cache_specs(self, pc):
        dp = pc.dp_spec()
        if self.kind == "mamba":
            return mamba.cache_specs(dp)
        return attention.cache_specs(dp)

    def apply_decode(self, params, x, cache, cache_len, pc, cfg,
                     shared_params=None, q_valid=None, layer=None):
        """One decode chunk through this layer, reading ``cache`` only:
        this layer's cache, or with ``layer`` (an int32 index) the stack of
        the scan's layers, of which it reads ``layer``.

        Returns (x, update): for attention the chunk's new cache rows, for
        mamba the whole new state; :meth:`write_cache` applies it."""
        mixer_params = shared_params if self.shared else params["mixer"]
        b, c = x.shape[0], x.shape[1]
        lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
        nv = (jnp.full((b,), c, jnp.int32) if q_valid is None
              else jnp.asarray(q_valid, jnp.int32))
        with jax.named_scope(self.mixer_scope):
            if self.kind == "mamba":
                if layer is not None:
                    cache = jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
                        cache)
                full = mamba.specs(cfg, pc.tp, pc.dp_spec())
                sp = {k: pc.manual(v) for k, v in full.items()}
                cs = {k: pc.manual(v) for k, v in mamba.cache_specs(pc.dp_spec()).items()}
                x, update = pc.smap(
                    lambda p_, x_, c_, n_: mamba.apply_decode_chunk(
                        p_, x_, c_, pc, cfg, q_valid=n_),
                    in_specs=(sp, P(None, None, None), cs, P(None)),
                    out_specs=(P(None, None, None), cs),
                )(pc.use_gather(mixer_params, full), x, cache, nv)
            else:
                full = attention.specs(cfg, pc.tp, pc.dp_spec())
                sp = {k: pc.manual(v) for k, v in full.items()}
                lead = () if layer is None else (None,)
                cs = {k: pc.manual(P(*lead, *v)) for k, v in
                      attention.cache_specs(pc.dp_spec()).items()}
                rs = {k: pc.manual(v) for k, v in
                      attention.rows_specs(pc.dp_spec()).items()}
                at = jnp.int32(0) if layer is None else jnp.asarray(layer, jnp.int32)
                x, update = pc.smap(
                    lambda p_, x_, c_, l_, n_, i_: attention.apply_decode(
                        p_, x_, c_, l_, pc, cfg, window=self.window,
                        rope_theta=self.theta, q_valid=n_,
                        layer=None if layer is None else i_),
                    in_specs=(sp, P(None, None, None), cs, P(None), P(None), P()),
                    out_specs=(P(None, None, None), rs),
                )(pc.use_gather(mixer_params, full), x, cache, lens, nv, at)
        return self.ffn_decode(params, x, pc, cfg), update

    def ffn_decode(self, params, x, pc, cfg):
        """This layer's FFN (if any) on a decode chunk x: [B, C, D]."""
        if self.ffn_kind == "mlp":
            full = ffn.specs(cfg, pc.tp, pc.dp_spec())
            sp = {k: pc.manual(v) for k, v in full.items()}
            with jax.named_scope("mlp"):
                x = pc.smap(
                    lambda p_, x_: ffn.apply_decode(p_, x_, pc, cfg),
                    in_specs=(sp, P(None, None, None)),
                    out_specs=P(None, None, None),
                )(pc.use_gather(params["ffn"], full), x)
        elif self.ffn_kind == "moe":
            full = moe.specs(cfg, pc.tp, pc.dp_spec())
            sp = jax.tree_util.tree_map(
                pc.manual, full, is_leaf=lambda v: isinstance(v, P))
            with jax.named_scope("moe"):
                x = pc.smap(
                    lambda p_, x_: moe.apply_decode(p_, x_, pc, cfg),
                    in_specs=(sp, P(None, None, None)),
                    out_specs=P(None, None, None),
                )(pc.use_gather(params["ffn"], full), x)
        return x

    def write_cache(self, cache, update, pc):
        """This layer's cache after :meth:`apply_decode`'s ``update``, over
        any leading layer axis: attention writes its new rows into the
        cache; a mamba state is small and rewritten whole by its recurrence,
        so the update is the new cache."""
        if self.kind == "mamba":
            return update
        return attention.write_rows(cache, update, pc, window=self.window)


def _layer_def(cfg, kind: str) -> LayerDef:
    theta_local = getattr(cfg, "rope_theta_local", 1e4)
    if kind == "mamba":
        return LayerDef("mamba", None, None, 0.0)
    if kind == "shared_attn":
        return LayerDef("shared_attn", "mlp", None, cfg.rope_theta, shared=True)
    window = cfg.local_window if kind == "attn_local" else None
    theta = theta_local if kind == "attn_local" else cfg.rope_theta
    if kind == "attn_dense":
        return LayerDef("attn_dense", "mlp", None, cfg.rope_theta)
    ffn_kind = None
    if cfg.moe is not None:
        ffn_kind = "moe"
    elif cfg.d_ff:
        ffn_kind = "mlp"
    return LayerDef(kind, ffn_kind, window, theta)


def layer_plan(cfg) -> Tuple[List[LayerDef], List[LayerDef], int, List[LayerDef]]:
    """(prefix_defs, unit_defs, n_units, suffix_defs)."""
    period = len(cfg.pattern)
    k0 = cfg.moe.first_k_dense if cfg.moe else 0
    prefix = [_layer_def(cfg, cfg.layer_kind(i)) for i in range(k0)]
    remaining = cfg.n_layers - k0
    n_units = remaining // period
    unit = [_layer_def(cfg, cfg.pattern[j]) for j in range(period)]
    n_suffix = remaining - n_units * period
    suffix = [_layer_def(cfg, cfg.pattern[j]) for j in range(n_suffix)]
    return prefix, unit, n_units, suffix


def _uses_shared(cfg) -> bool:
    return any(k == "shared_attn" for k in cfg.pattern)


def _gathered_head(params, cfg, pc):
    """LM head with ZeRO use-time gather of the dp-sharded dim."""
    from jax.sharding import PartitionSpec as _P

    if cfg.tie_embeddings:
        emb = jax.lax.with_sharding_constraint(
            params["embed"],
            jax.sharding.NamedSharding(pc.mesh, _P("model", None)))
        return emb.T
    return jax.lax.with_sharding_constraint(
        params["lm_head"],
        jax.sharding.NamedSharding(pc.mesh, _P(None, "model")))


# -----------------------------------------------------------------------------
# init / specs
# -----------------------------------------------------------------------------

def padded_vocab(cfg, pc) -> int:
    """Vocab rows padded to the TP degree (uneven vocabs e.g. 49155)."""
    v, tp = cfg.vocab_size, pc.tp
    return -(-v // tp) * tp


def init(key, cfg, pc: ParallelContext, dtype=jnp.bfloat16):
    prefix, unit, n_units, suffix = layer_plan(cfg)
    v_pad = padded_vocab(cfg, pc)
    ks = iter(jax.random.split(key, 8 + len(prefix) + len(suffix)))
    params: Dict[str, Any] = {
        "embed": emb_init(next(ks), (v_pad, cfg.d_model), dtype),
        "final_ln": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = emb_init(next(ks), (cfg.d_model, v_pad), dtype)
    if _uses_shared(cfg):
        params["shared_attn"] = attention.init(next(ks), cfg, pc.tp, dtype)

    params["prefix"] = [d.init(next(ks), cfg, pc, dtype) for d in prefix]
    params["suffix"] = [d.init(next(ks), cfg, pc, dtype) for d in suffix]

    if n_units:
        unit_key = next(ks)

        def one_unit(k):
            kk = jax.random.split(k, len(unit))
            return [d.init(kk[i], cfg, pc, dtype) for i, d in enumerate(unit)]

        params["scan"] = jax.vmap(one_unit)(jax.random.split(unit_key, n_units))
    return params


def specs(cfg, pc: ParallelContext):
    prefix, unit, n_units, suffix = layer_plan(cfg)
    dp = pc.dp_spec()
    s: Dict[str, Any] = {
        "embed": P("model", dp),
        "final_ln": P(None),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = P(dp, "model")
    if _uses_shared(cfg):
        s["shared_attn"] = attention.specs(cfg, pc.tp, dp)
    s["prefix"] = [d.specs(cfg, pc) for d in prefix]
    s["suffix"] = [d.specs(cfg, pc) for d in suffix]
    if n_units:
        def stack_spec(spec):
            # scanned params have a leading layer axis (unsharded)
            return P(*((None,) + tuple(spec)))

        s["scan"] = [
            jax.tree_util.tree_map(stack_spec, d.specs(cfg, pc),
                                   is_leaf=lambda v: isinstance(v, P))
            for d in unit
        ]
    return s


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
    """Pytree of 0/1 masks (or None) matching params, for padded-head params."""
    prefix, unit, n_units, suffix = layer_plan(cfg)
    m: Dict[str, Any] = {"embed": None, "final_ln": None}
    if not cfg.tie_embeddings:
        m["lm_head"] = None
    if _uses_shared(cfg):
        am = attention.grad_masks(cfg, pc.tp)
        m["shared_attn"] = am if am is not None else jax.tree_util.tree_map(
            lambda _: None, attention.specs(cfg, pc.tp, pc.dp_spec()))
    m["prefix"] = [d.grad_masks(cfg, pc) for d in prefix]
    m["suffix"] = [d.grad_masks(cfg, pc) for d in suffix]
    if n_units:
        m["scan"] = [d.grad_masks(cfg, pc) for d in unit]  # broadcast over layer axis
    return m


# -----------------------------------------------------------------------------
# forward (train / prefill)
# -----------------------------------------------------------------------------

def _seam_chain(defs, plist, x, pc, cfg, shared, aux_total):
    """Run a python-level list of layers, fusing RS->AG seams between
    consecutive eligible layers (attention + dense MLP); an ineligible layer
    (mamba, MoE) breaks the chain and runs the unfused body.  Chains live
    within one python-level segment only — a lax.scan carry boundary cannot
    carry a half-open seam, so prefix / each scan unit / suffix chain
    independently.
    """
    qkv = None
    n = len(defs)
    for i, (d, p) in enumerate(zip(defs, plist)):
        if not d.seam_eligible():
            x, aux = d.apply_seq(p, x, pc, cfg, shared_params=shared)
            aux_total = aux_total + aux
            continue
        next_mixer = None
        if i + 1 < n and defs[i + 1].seam_eligible():
            nd, np_ = defs[i + 1], plist[i + 1]
            next_mixer = shared if nd.shared else np_["mixer"]
        x, aux, qkv = d.apply_seq_fused(p, x, pc, cfg, shared_params=shared,
                                        qkv=qkv, next_mixer=next_mixer)
        aux_total = aux_total + aux
    return x, aux_total


def embed_tokens(params, cfg, tokens, embeds=None):
    """tokens: [B, S] int32 (or None); embeds: [B, S0, D] stub-frontend prefix."""
    parts = []
    if embeds is not None:
        parts.append(embeds.astype(params["embed"].dtype))
    if tokens is not None:
        parts.append(jnp.take(params["embed"], tokens, axis=0))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.family in ("vlm",) or cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def forward(params, cfg, pc: ParallelContext, tokens, embeds=None,
            remat_policy: str = "none", unroll: bool = False):
    """Returns (logits [B, S, V], aux_loss scalar).

    ``unroll`` replaces the layer scan with a python loop — used by the
    dry-run cost analysis (XLA counts while bodies once) and for small-depth
    debugging; numerically identical.

    With ``pc.fuse_seams`` consecutive attention+MLP layers chain their
    RS->AG seams into shared ring passes (see :func:`_seam_chain`); chains
    reset at lax.scan carry boundaries.
    """
    from repro.nn.layers import rms_norm

    prefix, unit, n_units, suffix = layer_plan(cfg)
    with jax.named_scope("embed"):
        x = embed_tokens(params, cfg, tokens, embeds)
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(pc.mesh, P(pc.dp_spec(), "model", None)))

    shared = params.get("shared_attn")
    aux_total = jnp.zeros((), jnp.float32)

    if pc.fuse_seams:
        x, aux_total = _seam_chain(prefix, params["prefix"], x, pc, cfg,
                                   shared, aux_total)
    else:
        for d, p in zip(prefix, params["prefix"]):
            x, aux = d.apply_seq(p, x, pc, cfg, shared_params=shared)
            aux_total = aux_total + aux

    if n_units:
        def unit_body(carry, unit_params):
            h, aux_acc = carry
            if pc.fuse_seams:
                plist = [unit_params[i] for i in range(len(unit))]
                h, aux_acc = _seam_chain(unit, plist, h, pc, cfg,
                                         shared, aux_acc)
            else:
                for i, d in enumerate(unit):
                    h, aux = d.apply_seq(unit_params[i], h, pc, cfg,
                                         shared_params=shared)
                    aux_acc = aux_acc + aux
            return (h, aux_acc), None

        body = unit_body
        if remat_policy != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if remat_policy == "dots" else None)
            body = jax.checkpoint(unit_body, policy=policy)

        with jax.named_scope("layers"):
            if unroll:
                for u in range(n_units):
                    up = jax.tree_util.tree_map(lambda a: a[u], params["scan"])
                    (x, aux_total), _ = body((x, aux_total), up)
            else:
                (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["scan"])

    if pc.fuse_seams:
        x, aux_total = _seam_chain(suffix, params["suffix"], x, pc, cfg,
                                   shared, aux_total)
    else:
        for d, p in zip(suffix, params["suffix"]):
            x, aux = d.apply_seq(p, x, pc, cfg, shared_params=shared)
            aux_total = aux_total + aux

    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    with jax.named_scope("head"):
        head = _gathered_head(params, cfg, pc)
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        logits = jax.lax.with_sharding_constraint(
            logits, jax.sharding.NamedSharding(pc.mesh, P(pc.dp_spec(), None, "model")))
        return logits[..., : cfg.vocab_size], aux_total


def prefill(params, cfg, pc: ParallelContext, tokens, embeds=None, *,
            max_len: int, unroll: bool = False):
    """Forward pass that also fills decode caches (serve-path prefill).

    Returns (logits [B, S, V], caches) — decode continues at position S.
    """
    from repro.nn.layers import rms_norm

    prefix, unit, n_units, suffix = layer_plan(cfg)
    with jax.named_scope("embed"):
        x = embed_tokens(params, cfg, tokens, embeds)
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(pc.mesh, P(pc.dp_spec(), "model", None)))
    shared = params.get("shared_attn")

    pre_caches = []
    for d, p in zip(prefix, params["prefix"]):
        x, _, c = d.apply_prefill(p, x, pc, cfg, max_len, shared_params=shared)
        pre_caches.append(c)

    scan_caches = None
    if n_units:
        def unit_body(h, unit_params):
            caches = []
            for i, d in enumerate(unit):
                h, _, c = d.apply_prefill(unit_params[i], h, pc, cfg, max_len,
                                          shared_params=shared)
                caches.append(c)
            return h, caches

        with jax.named_scope("layers"):
            if unroll:
                collected = []
                for u in range(n_units):
                    up = jax.tree_util.tree_map(lambda a: a[u], params["scan"])
                    x, cs_u = unit_body(x, up)
                    collected.append(cs_u)
                scan_caches = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *collected)
            else:
                x, scan_caches = jax.lax.scan(unit_body, x, params["scan"])

    suf_caches = []
    for d, p in zip(suffix, params["suffix"]):
        x, _, c = d.apply_prefill(p, x, pc, cfg, max_len, shared_params=shared)
        suf_caches.append(c)

    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    with jax.named_scope("head"):
        head = _gathered_head(params, cfg, pc)
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))[..., : cfg.vocab_size]
    return logits, {"prefix": pre_caches, "scan": scan_caches, "suffix": suf_caches}


# -----------------------------------------------------------------------------
# decode
# -----------------------------------------------------------------------------

def init_caches(cfg, pc, batch, max_len, dtype=jnp.bfloat16):
    prefix, unit, n_units, suffix = layer_plan(cfg)
    caches = {
        "prefix": [d.init_cache(cfg, pc, batch, max_len, dtype) for d in prefix],
        "suffix": [d.init_cache(cfg, pc, batch, max_len, dtype) for d in suffix],
    }
    if n_units:
        caches["scan"] = [
            jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n_units,) + a.shape).copy(),
                d.init_cache(cfg, pc, batch, max_len, dtype))
            for d in unit
        ]
    return caches


def cache_specs(cfg, pc):
    prefix, unit, n_units, suffix = layer_plan(cfg)
    cs = {
        "prefix": [d.cache_specs(pc) for d in prefix],
        "suffix": [d.cache_specs(pc) for d in suffix],
    }
    if n_units:
        cs["scan"] = [
            jax.tree_util.tree_map(lambda sp: P(*((None,) + tuple(sp))),
                                   d.cache_specs(pc),
                                   is_leaf=lambda v: isinstance(v, P))
            for d in unit
        ]
    return cs


def decode_step(params, caches, cfg, pc: ParallelContext, tokens, cache_len,
                unroll: bool = False, q_valid=None):
    """One decode step advancing every slot by up to C tokens.

    tokens: [B, C] int32 (C == 1 is plain decode; C > 1 a prefill chunk);
    cache_len: traced scalar or per-slot [B] vector; ``q_valid`` (optional
    [B] int) marks how many of the C rows are real per slot — rows past it
    leave cache/state untouched and their logits are garbage.

    Returns (logits [B, C, V], new_caches).
    """
    from repro.nn.layers import rms_norm

    prefix, unit, n_units, suffix = layer_plan(cfg)
    with jax.named_scope("embed"):
        x = embed_tokens(params, cfg, tokens)
    shared = params.get("shared_attn")

    new_prefix = []
    for d, p, c in zip(prefix, params["prefix"], caches["prefix"]):
        x, u = d.apply_decode(p, x, c, cache_len, pc, cfg,
                              shared_params=shared, q_valid=q_valid)
        new_prefix.append(d.write_cache(c, u, pc))

    new_scan = caches.get("scan")
    if n_units:
        # each layer reads the whole stack of caches at its index (a slice
        # taken as the scan's xs would be copied out for the decode-attention
        # kernel) and returns only its update; one write after the scan puts
        # them in, in place
        def unit_body(h, xs):
            unit_params, idx = xs
            updates = []
            for i, d in enumerate(unit):
                h, u = d.apply_decode(unit_params[i], h, caches["scan"][i],
                                      cache_len, pc, cfg, shared_params=shared,
                                      q_valid=q_valid, layer=idx)
                updates.append(u)
            return h, updates

        with jax.named_scope("layers"):
            if unroll:
                collected = []
                for u in range(n_units):
                    up = jax.tree_util.tree_map(lambda a: a[u], params["scan"])
                    x, us = unit_body(x, (up, jnp.int32(u)))
                    collected.append(us)
                updates = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                                 *collected)
            else:
                x, updates = jax.lax.scan(
                    unit_body, x, (params["scan"], jnp.arange(n_units, dtype=jnp.int32)))
            new_scan = [d.write_cache(c, u, pc)
                        for d, c, u in zip(unit, caches["scan"], updates)]

    new_suffix = []
    for d, p, c in zip(suffix, params["suffix"], caches["suffix"]):
        x, u = d.apply_decode(p, x, c, cache_len, pc, cfg,
                              shared_params=shared, q_valid=q_valid)
        new_suffix.append(d.write_cache(c, u, pc))

    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    with jax.named_scope("head"):
        head = _gathered_head(params, cfg, pc)
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))[..., : cfg.vocab_size]
    return logits, {"prefix": new_prefix, "scan": new_scan, "suffix": new_suffix}
