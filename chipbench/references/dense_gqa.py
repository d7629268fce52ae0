"""The plain reference of the dense grouped-query-attention decoder family
(Llama, SmolLM, Qwen2): the configured model in straightforward
``jax.numpy``, with what the harness must know of the program for this
family.  A configuration file names its family under ``"reference"``; a
family of another shape brings a module of its own beside this one.

It imports nothing of the program and takes nothing the program made.  Its
weights come from :mod:`chipbench.weights` and the seed, drawn again here; it
reads the program's storage layout of those weights and of the serving
engine's KV cache, which are part of the program's interface and are written
out below:

* attention ``wq`` [d, H*hd] holds the query heads in order; ``wkv``
  [d, KV*2*hd] holds, for each of the ``tp`` column shards, the shard's key
  heads and then its value heads; ``wo`` [H*hd, d]; ``bq``/``bkv`` as their
  weights' columns;
* the MLP's ``w_gu`` [d, 2*f] holds, for each shard, the shard's gate
  columns and then its up columns; ``w_down`` [f, d];
* norm scales multiply by (1 + scale); layers are stacked on a leading axis
  under ``scan/0``; the head is ``lm_head`` [d, V], or ``embed`` transposed
  where the embeddings are tied;
* the slot pool's cache is ``caches["scan"][0]``, keys ``k`` and values
  ``v`` of [layers, slots, KV, max_len, hd], position p of a slot in row p,
  after the rotary embedding.

The layer is the published pre-norm decoder layer: RMSNorm, grouped-query
attention with rotary embeddings and causal masking, residual; RMSNorm,
SiLU-gated MLP, residual.  The program rotates adjacent pairs of each head's
dimensions where the published models rotate its two halves; with random
weights that is a fixed permutation of the query and key columns, and the
reference rotates pairs to compute the same function.

Every product runs at JAX's default precision, the precision the
configurations state.  ``dtype`` bfloat16 gives the control: the same
reference with weights and activations in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.flops import Dims, dims_of

__all__ = ["ARCH_FIELDS", "dims", "options", "tree_shapes", "stacked", "cached_kv",
           "forward_rows", "first_layer_kv", "train_readings", "token_gaps", "adamw_lr"]

LAYER = "scan/0"

# configuration file keys (HF names) -> the program's ArchConfig fields
ARCH_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "hidden_act": "act", "qkv_bias": "qkv_bias",
}


def dims(model: dict) -> Dims:
    """The shapes the yardstick counts, from the configuration's ``model``."""
    return dims_of(model)


def options(config: dict) -> dict:
    """Keyword arguments of the reference's entries, from a configuration file."""
    m = config["model"]
    return dict(tp=int(config["tp"]), tied=bool(m["tie_word_embeddings"]),
                theta=float(m["rope_theta"]), eps=float(m["rms_norm_eps"]))


def tree_shapes(dm: Dims, tp: int, tied: bool) -> dict:
    """Name -> shape of every leaf of the program's parameter tree."""
    d, H, KV, hd, f, L = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.d_ff, dm.layers
    v_pad = -(-dm.vocab // tp) * tp
    s = {"embed": (v_pad, d), "final_ln": (d,)}
    if not tied:
        s["lm_head"] = (d, v_pad)
    layer = {"mixer/ln": (d,), "mixer/wq": (d, H * hd), "mixer/wkv": (d, KV * 2 * hd),
             "mixer/wo": (H * hd, d), "ffn/ln": (d,), "ffn/w_gu": (d, 2 * f),
             "ffn/w_down": (f, d)}
    if dm.qkv_bias:
        layer.update({"mixer/bq": (H * hd,), "mixer/bkv": (KV * 2 * hd,)})
    for k, v in layer.items():
        s[f"{LAYER}/{k}"] = (L,) + v
    return s


def stacked(name: str) -> bool:
    return name.startswith(LAYER + "/")


@jax.jit
def _slot_kv(c, layer, slot):
    return c["k"][layer, slot], c["v"][layer, slot]


def cached_kv(caches, layer: int, slot: int, rows: int):
    """Keys and values [rows, KV, hd] (float32 numpy) that the serving
    engine's slot pool holds for ``slot`` at ``layer``."""
    k, v = jax.device_get(_slot_kv(caches["scan"][0], jnp.int32(layer), jnp.int32(slot)))
    return (np.asarray(k[:, :rows], np.float32).transpose(1, 0, 2),
            np.asarray(v[:, :rows], np.float32).transpose(1, 0, 2))


def _layer_shapes(dm, tp, tied):
    return {k[len(LAYER) + 1:]: v[1:] for k, v in tree_shapes(dm, tp, tied).items()
            if stacked(k)}


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, pos, theta):
    """x [B, S, n, hd]; pos [S]; rotates adjacent pairs of dimensions."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _split_layer(p, dm: Dims, tp: int):
    d, H, KV, hd, f = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.d_ff
    if H % tp or KV % tp or f % tp:
        raise ValueError(f"heads {H}/{KV} and d_ff {f} must divide over tp={tp}")
    kv_loc, f_loc = KV // tp, f // tp
    wkv = p["mixer/wkv"].reshape(d, tp, 2, kv_loc, hd)
    gu = p["ffn/w_gu"].reshape(d, tp, 2, f_loc)
    out = {
        "ln1": p["mixer/ln"], "ln2": p["ffn/ln"],
        "wq": p["mixer/wq"].reshape(d, H, hd),
        "wk": wkv[:, :, 0].reshape(d, KV, hd), "wv": wkv[:, :, 1].reshape(d, KV, hd),
        "wo": p["mixer/wo"].reshape(H, hd, d),
        "gate": gu[:, :, 0].reshape(d, f), "up": gu[:, :, 1].reshape(d, f),
        "down": p["ffn/w_down"],
    }
    if dm.qkv_bias:
        bkv = p["mixer/bkv"].reshape(tp, 2, kv_loc, hd)
        out.update(bq=p["mixer/bq"].reshape(H, hd), bk=bkv[:, 0].reshape(KV, hd),
                   bv=bkv[:, 1].reshape(KV, hd))
    return out


def _attention(q, k, v, q_block):
    """Causal grouped-query attention, queries in blocks of ``q_block``.
    q [B, S, H, hd] (already scaled); k, v [B, S, KV, hd]."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    nb = s // q_block
    qb = q.reshape(b, nb, q_block, kv, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(s)

    def one(args):
        i, qi = args
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, preferred_element_type=jnp.float32)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.where((qpos[:, None] >= kpos[None, :])[None, None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    o = jax.lax.map(one, (jnp.arange(nb), qb))  # [nb, B, q_block, KV, G, hd]
    return o.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd)


def _qkv(w, x, dm: Dims, theta, eps):
    """Queries (scaled), keys and values [B, S, heads, hd] of a layer, after
    the rotary embedding."""
    pos = jnp.arange(x.shape[1])
    h = _rms(x, w["ln1"], eps)
    q = jnp.einsum("bsd,dhe->bshe", h, w["wq"])
    k = jnp.einsum("bsd,dhe->bshe", h, w["wk"])
    v = jnp.einsum("bsd,dhe->bshe", h, w["wv"])
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q, pos, theta) * jnp.asarray(dm.head_dim ** -0.5, x.dtype)
    return q, _rope(k, pos, theta), v


def _layer(w, x, dm: Dims, theta, eps, q_block):
    q, k, v = _qkv(w, x, dm, theta, eps)
    o = _attention(q, k, v, q_block)
    x = x + jnp.einsum("bshe,hed->bsd", o, w["wo"])
    h = _rms(x, w["ln2"], eps)
    a = jax.nn.silu(h @ w["gate"]) * (h @ w["up"])
    return x + a @ w["down"]


@functools.partial(jax.jit, static_argnames=("dm", "tp", "tied", "theta", "eps",
                                              "q_block", "dtype"))
def _layer_from_seed(key, i, x, *, dm, tp, tied, theta, eps, q_block, dtype):
    return _layer(_layer_weights(key, i, dm, tp, tied, dtype), x, dm, theta, eps, q_block)


def _layer_weights(key, i, dm, tp, tied, dtype):
    shapes = _layer_shapes(dm, tp, tied)
    p = {n: weights.leaf(key, f"{LAYER}/{n}", shp, i).astype(dtype) for n, shp in shapes.items()}
    return _split_layer(p, dm, tp)


@functools.partial(jax.jit, static_argnames=("dm", "tp", "tied", "theta", "eps", "dtype"))
def _kv_from_seed(key, i, x, *, dm, tp, tied, theta, eps, dtype):
    _, k, v = _qkv(_layer_weights(key, i, dm, tp, tied, dtype), x, dm, theta, eps)
    return k.astype(jnp.float32), v.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dm", "tp", "tied", "dtype"))
def _embed_from_seed(key, tokens, *, dm, tp, tied, dtype):
    shp = tree_shapes(dm, tp, tied)["embed"]
    return jnp.take(weights.leaf(key, "embed", shp).astype(dtype), tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("dm", "tp", "tied", "eps", "dtype"))
def _logits_from_seed(key, h, *, dm, tp, tied, eps, dtype):
    shapes = tree_shapes(dm, tp, tied)
    h = _rms(h, weights.leaf(key, "final_ln", shapes["final_ln"]).astype(dtype), eps)
    if tied:
        head = weights.leaf(key, "embed", shapes["embed"]).astype(dtype).T
    else:
        head = weights.leaf(key, "lm_head", shapes["lm_head"]).astype(dtype)
    return jnp.einsum("brd,dv->brv", h, head[:, : dm.vocab]).astype(jnp.float32)


def forward_rows(key, dm: Dims, tokens, rows, *, tp: int, tied: bool, theta: float,
                 eps: float, dtype=jnp.float32, q_block: int = 512):
    """Logits [B, R, V] (float32) at positions ``rows`` [B, R] of a causal
    forward over ``tokens`` [B, S], one layer at a time with each layer's
    weights drawn again from ``key``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    q_block = min(q_block, tokens.shape[1])
    x = _embed_from_seed(key, tokens, dm=dm, tp=tp, tied=tied, dtype=dtype)
    for i in range(dm.layers):
        x = _layer_from_seed(key, jnp.int32(i), x, dm=dm, tp=tp, tied=tied, theta=theta,
                             eps=eps, q_block=q_block, dtype=dtype)
    h = jnp.take_along_axis(x, jnp.asarray(rows, jnp.int32)[..., None], axis=1)
    return _logits_from_seed(key, h, dm=dm, tp=tp, tied=tied, eps=eps, dtype=dtype)


def first_layer_kv(key, dm: Dims, tokens, *, tp: int, tied: bool, theta: float,
                   eps: float, dtype=jnp.float32):
    """Keys and values [B, S, KV, hd] (float32) of the first layer over
    ``tokens`` [B, S]: what a serving cache holds there."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed_from_seed(key, tokens, dm=dm, tp=tp, tied=tied, dtype=dtype)
    return _kv_from_seed(key, jnp.int32(0), x, dm=dm, tp=tp, tied=tied, theta=theta,
                         eps=eps, dtype=dtype)


def token_gaps(logits, tokens):
    """How far each chosen token's logit lies below the best: logits [..., V]
    float32, tokens [...]; returns [...]."""
    chosen = jnp.take_along_axis(logits, jnp.asarray(tokens, jnp.int32)[..., None],
                                 axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - chosen


# --------------------------------------------------------------------- training

def adamw_lr(opt: dict, step: int) -> float:
    """Learning rate at ``step`` (1-based): linear warm-up, then cosine down
    to ``min_lr_ratio`` of the peak over ``total_steps``."""
    import math

    warm = min(1.0, step / max(1, opt["warmup_steps"]))
    prog = (step - opt["warmup_steps"]) / max(1, opt["total_steps"] - opt["warmup_steps"])
    prog = min(max(prog, 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


def _decays(name: str) -> bool:
    """Decoupled weight decay on weight matrices and embeddings, not on norm
    scales or biases."""
    last = name.rsplit("/", 1)[-1]
    return last not in ("ln", "final_ln") and not last.startswith("b")


def _train_loss(params, batch, dm, tp, tied, theta, eps, dtype, q_block):
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = jnp.take(p["embed"], batch["inputs"], axis=0)
    layer_p = {k[len(LAYER) + 1:]: v for k, v in p.items() if stacked(k)}

    @jax.checkpoint
    def body(x, lp):
        return _layer(_split_layer(lp, dm, tp), x, dm, theta, eps, q_block), None

    x, _ = jax.lax.scan(body, x, layer_p)
    x = _rms(x, p["final_ln"], eps)
    head = p["embed"].T if tied else p["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head[:, : dm.vocab]).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def leaf_norms(tree) -> dict:
    """Norm of each leaf, a stacked leaf's layers each on their own:
    name -> float32 array ([L] for a stacked leaf, [] otherwise)."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if stacked(k):
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(range(1, v.ndim))))
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


def train_readings(key, dm: Dims, batches, opt: dict, *, tp: int, tied: bool,
                   theta: float, eps: float, dtype=jnp.float32, param_dtype=jnp.float32,
                   q_block: int = 512, fault=None):
    """Three AdamW steps of the reference from the seeded weights.

    Returns {"loss": [3 floats], "grad1": leaf norms of the first clipped
    gradient, "update3": leaf norms of the parameters' change after three
    steps}.  ``fault`` plants one of the faults the check must catch, in the
    reference put in the program's place: "half_batch" (the loss over half
    the rows).  ``dtype`` is the precision of the forward and backward
    passes and ``param_dtype`` that the parameters are kept in (the optimizer's
    moments stay float32); both bfloat16 is the control.
    """
    shapes = tree_shapes(dm, tp, tied)
    params = jax.jit(lambda k: weights.build(
        k, {n: jax.ShapeDtypeStruct(s, jnp.float32) for n, s in shapes.items()},
        stacked=stacked))(key)
    p0 = params
    params = {k: v.astype(param_dtype) for k, v in params.items()}
    mu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    nu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    q_block = min(q_block, batches[0]["inputs"].shape[1])

    def loss_fn(p, batch):
        if fault == "half_batch":
            half = batch["inputs"].shape[0] // 2
            batch = {k: v[:half] for k, v in batch.items()}
        return _train_loss(p, batch, dm, tp, tied, theta, eps, dtype, q_block)

    @jax.jit
    def step(p, mu, nu, batch, lr, t):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        g = {k: v.astype(jnp.float32) for k, v in g.items()}
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in g.values()))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        b1c = 1 - opt["b1"] ** t
        b2c = 1 - opt["b2"] ** t
        newp, newmu, newnu, cg = {}, {}, {}, {}
        for k in p:
            gk = g[k] * clip
            cg[k] = gk
            m = opt["b1"] * mu[k] + (1 - opt["b1"]) * gk
            n = opt["b2"] * nu[k] + (1 - opt["b2"]) * jnp.square(gk)
            delta = (m / b1c) / (jnp.sqrt(n / b2c) + opt["eps"])
            if _decays(k):
                delta = delta + opt["weight_decay"] * p[k].astype(jnp.float32)
            newp[k] = (p[k].astype(jnp.float32) - lr * delta).astype(param_dtype)
            newmu[k], newnu[k] = m, n
        return newp, newmu, newnu, loss, leaf_norms(cg)

    losses, grad1 = [], None
    for t, batch in enumerate(batches[:3], start=1):
        batch = {k: jnp.asarray(v, jnp.int32) for k, v in batch.items()}
        params, mu, nu, loss, gn = step(params, mu, nu, batch,
                                        jnp.float32(adamw_lr(opt, t)), jnp.float32(t))
        losses.append(float(loss))
        if t == 1:
            grad1 = jax.device_get(gn)
    update3 = jax.device_get(leaf_norms({k: params[k].astype(jnp.float32) - p0[k]
                                         for k in params}))
    return {"loss": losses, "grad1": grad1, "update3": update3}
