"""The decode step reads the KV cache where it lies and writes only the new
rows: the layer scan returns no stacked cache and no GQA-expanded copy of a
cache exists, and the step matches the algorithm that wrote each layer's
whole cache, expanded K/V with ``jnp.repeat`` and restacked the caches
(kept here as the reference)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.configs import get_config
from repro.models import lm
from repro.nn import attention
from repro.nn.layers import gqa_layout, rms_norm, rope
from repro.parallel.context import ParallelContext
from repro.parallel.sharding import place
from utils import reduce_config

# ---- the reference: each layer writes and returns its whole cache -----------


def _repeat_apply_decode(params, x, cache, cache_len, pc, cfg, *, window=None,
                         rope_theta=None, q_valid=None):
    """Attention decode that scatters the chunk into the cache, then reads
    the cache with K/V expanded to every query head."""
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, pc.tp)
    hd = cfg.hd
    b, c, _ = x.shape
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    nv = jnp.asarray(q_valid, jnp.int32)
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    qkv = jnp.einsum("bsd,dn->bsn", h, jnp.concatenate([params["wq"], params["wkv"]], 1))
    if "bq" in params:
        qkv = qkv + jnp.concatenate([params["bq"], params["bkv"]])
    qkv = qkv.reshape(b, c, lay.h_loc + 2 * lay.kv_loc, hd)
    q = qkv[:, :, : lay.h_loc]
    k = qkv[:, :, lay.h_loc: lay.h_loc + lay.kv_loc]
    v = qkv[:, :, lay.h_loc + lay.kv_loc:]
    pos = lens[:, None] + jnp.arange(c)[None, :]
    q, k = rope(q, k, pos, rope_theta if rope_theta is not None else cfg.rope_theta)

    size = cache["k"].shape[2]
    ring = window is not None and size <= window
    slots = jnp.remainder(pos, size) if ring else pos
    slots = jnp.where(jnp.arange(c)[None, :] < nv[:, None], slots, size)

    def write(buf, vals, idx):
        return buf.at[:, idx].set(vals, mode="drop")

    ck = jax.vmap(write)(cache["k"], k.transpose(0, 2, 1, 3), slots)
    cv = jax.vmap(write)(cache["v"], v.transpose(0, 2, 1, 3), slots)

    rep = lay.h_loc // lay.kv_loc
    kk = jnp.repeat(cache["k"], rep, axis=1).astype(jnp.float32)
    vv = jnp.repeat(cache["v"], rep, axis=1).astype(jnp.float32)
    kc = jnp.repeat(k, rep, axis=2)
    vc = jnp.repeat(v, rep, axis=2)
    qf = (q.transpose(0, 2, 1, 3) * hd ** -0.5).astype(jnp.float32)
    s1 = jnp.einsum("bhqd,bhkd->bhqk", qf, kk)
    j = jnp.arange(size)
    if ring:
        last = lens - 1
        p_j = last[:, None] - jnp.remainder(last[:, None] - j[None, :], size)
        m1 = (p_j >= 0)[:, None, :] & ((pos[:, :, None] - p_j[:, None, :]) < window)
    else:
        m1 = jnp.broadcast_to((j[None, :] < lens[:, None])[:, None, :], (b, c, size))
        if window is not None:
            m1 = m1 & ((pos[:, :, None] - j[None, None, :]) < window)
    s1 = jnp.where(m1[:, None], s1, -1e30)
    s2 = jnp.einsum("bhqd,bkhd->bhqk", qf, kc.astype(jnp.float32))
    qi = jnp.arange(c)
    m2 = (qi[None, :, None] >= qi[None, None, :]) & (qi[None, None, :] < nv[:, None, None])
    if window is not None:
        m2 = m2 & ((qi[None, :, None] - qi[None, None, :]) < window)
    s2 = jnp.where(m2[:, None], s2, -1e30)
    p = jax.nn.softmax(jnp.concatenate([s1, s2], axis=-1), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p[..., :size], vv)
    o = o + jnp.einsum("bhqk,bkhd->bhqd", p[..., size:], vc.astype(jnp.float32))
    o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, c, lay.h_loc * hd)
    return x + pc.psum(jnp.einsum("bsn,nd->bsd", o, params["wo"])), {"k": ck, "v": cv}


def _reference_layer(d, p, x, cache, cache_len, pc, cfg, shared, q_valid):
    if d.kind == "mamba":  # a mamba layer returns its whole new state
        return d.apply_decode(p, x, cache, cache_len, pc, cfg, q_valid=q_valid)
    b = x.shape[0]
    full = attention.specs(cfg, pc.tp, pc.dp_spec())
    sp = {k: pc.manual(v) for k, v in full.items()}
    cs = {k: pc.manual(v) for k, v in attention.cache_specs(pc.dp_spec()).items()}
    x, cache = pc.smap(
        lambda p_, x_, c_, l_, n_: _repeat_apply_decode(
            p_, x_, c_, l_, pc, cfg, window=d.window, rope_theta=d.theta, q_valid=n_),
        in_specs=(sp, P(None, None, None), cs, P(None), P(None)),
        out_specs=(P(None, None, None), cs),
    )(pc.use_gather(shared if d.shared else p["mixer"], full), x, cache,
      jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,)), q_valid)
    return d.ffn_decode(p, x, pc, cfg), cache


def _reference_step(params, caches, cfg, pc, tokens, cache_len, q_valid):
    """Every layer returns its whole cache; the scan's caches are restacked."""
    prefix, unit, n_units, suffix = lm.layer_plan(cfg)
    shared = params.get("shared_attn")
    x = lm.embed_tokens(params, cfg, tokens)
    out = {"prefix": [], "suffix": []}
    for d, p, c in zip(prefix, params["prefix"], caches["prefix"]):
        x, c = _reference_layer(d, p, x, c, cache_len, pc, cfg, shared, q_valid)
        out["prefix"].append(c)
    stacked = []
    for u in range(n_units):
        at = lambda t: jax.tree_util.tree_map(lambda a: a[u], t)  # noqa: E731
        up, uc = at(params["scan"]), at(caches["scan"])
        new = []
        for i, d in enumerate(unit):
            x, c = _reference_layer(d, up[i], x, uc[i], cache_len, pc, cfg, shared, q_valid)
            new.append(c)
        stacked.append(new)
    out["scan"] = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stacked)
                   if n_units else caches.get("scan"))
    for d, p, c in zip(suffix, params["suffix"], caches["suffix"]):
        x, c = _reference_layer(d, p, x, c, cache_len, pc, cfg, shared, q_valid)
        out["suffix"].append(c)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = lm._gathered_head(params, cfg, pc)
    return jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))[..., : cfg.vocab_size], out


# ---- helpers -----------------------------------------------------------------

def _model(arch, pc, mesh, **over):
    cfg = dataclasses.replace(reduce_config(get_config(arch)), vocab_size=128, **over)
    params = place(lm.init(jax.random.PRNGKey(0), cfg, pc, jnp.float32), mesh,
                   lm.specs(cfg, pc))
    return cfg, params


def _filled_caches(cfg, pc, n_slots, max_len, seed=1):
    """Caches of seeded noise: every row, state and ring slot holds data."""
    caches = lm.init_caches(cfg, pc, n_slots, max_len, jnp.float32)
    leaves, tree = jax.tree_util.tree_flatten(caches)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


# ---- structure ---------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 3])
def test_decode_step_keeps_no_stacked_or_expanded_cache(chunk):
    """The layer scan outputs rows, not caches, and no GQA-expanded cache
    exists anywhere in the step (GQA with 2 query heads per KV head)."""
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    pc = ParallelContext(mesh=mesh, mode="overlap")
    cfg, params = _model("qwen2-72b", pc, mesh)
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, pc.tp)
    rep = lay.h_loc // lay.kv_loc
    assert rep > 1
    n_slots, max_len = 3, 24
    caches = lm.init_caches(cfg, pc, n_slots, max_len, jnp.float32)
    stacked = tuple(caches["scan"][0]["k"].shape)
    assert stacked == (cfg.n_layers, n_slots, lay.kv_loc, max_len, cfg.hd)
    tokens = jnp.zeros((n_slots, chunk), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, c, t, n: lm.decode_step(p, c, cfg, pc, t, n))(
        params, caches, tokens, jnp.zeros((n_slots,), jnp.int32)).jaxpr

    eqns = list(_eqns(jaxpr))
    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert scans
    for e in scans:
        assert stacked not in [tuple(v.aval.shape) for v in e.outvars]
    expanded = {(n_slots, lay.h_loc, max_len, cfg.hd),
                (n_slots, lay.kv_loc, rep, max_len, cfg.hd)}
    shapes = {tuple(v.aval.shape) for e in eqns for v in e.outvars
              if hasattr(v.aval, "shape")}
    assert not shapes & expanded


# ---- parity with the reference -------------------------------------------------

SHORT = (0, 5, 9, 13)
# rows fed to each of the 4 slots for a chunk width: slot 2 idle, others short
FEEDS = {1: (1, 1, 0, 1), 3: (3, 2, 0, 1)}
WIDE = {130: (130, 130, 0, 1)}  # chunks wider than a span of 128 cache rows
CASES = {
    # GQA, 2 query heads per KV head on each of 4 shards
    "gqa": ("qwen2-72b", {}, 24, SHORT, FEEDS),
    # sliding-window rings of 4 rows, which the positions wrap
    "ring": ("gemma3-27b", {"local_window": 4, "n_layers": 6}, 24, SHORT, FEEDS),
    # rings of 300 rows and a global cache of 320, longer than the spans the
    # write reads back: a chunk crosses row 128 and another wraps past row 299
    "long_ring": ("gemma3-27b", {"local_window": 300, "n_layers": 6}, 320,
                  (126, 299, 9, 298), FEEDS),
    # five mamba layers and a shared attention layer in the scanned unit
    "hybrid": ("zamba2-2.7b", {}, 24, SHORT, FEEDS),
    # caches of 400 rows: a chunk from row 0, one from row 127 across rows 128
    # and 256 (three spans' rows)
    "wide_gqa": ("qwen2-72b", {}, 400, (0, 127, 9, 270), WIDE),
    # rings of 520 rows, wider than a span: a chunk wraps past row 519,
    # another runs from row 255 across row 384
    "wide_ring": ("gemma3-27b", {"local_window": 520, "n_layers": 6}, 600,
                  (450, 255, 9, 300), WIDE),
}


@pytest.mark.parametrize("case,chunk", [(name, chunk) for name, case in CASES.items()
                                        for chunk in case[4]])
def test_decode_step_matches_write_then_expand(case, chunk, pc8, mesh8):
    """A decode pass (one row a slot) and chunks of prefill rows, against the
    reference from caches of noise: logits of the live rows, every cache,
    and the idle slot's rows and state left bit for bit."""
    arch, over, max_len, lens, feeds = CASES[case]
    cfg, params = _model(arch, pc8, mesh8, **over)
    n_slots = 4
    caches = _filled_caches(cfg, pc8, n_slots, max_len)
    lens = jnp.asarray(lens, jnp.int32)
    valid = jnp.asarray(feeds[chunk], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (n_slots, chunk), 0, cfg.vocab_size)

    step = jax.jit(lambda p, c, t, n, v: lm.decode_step(p, c, cfg, pc8, t, n, q_valid=v))
    ref = jax.jit(lambda p, c, t, n, v: _reference_step(p, c, cfg, pc8, t, n, v))
    logits, new = step(params, caches, tokens, lens, valid)
    want_logits, want = ref(params, caches, tokens, lens, valid)

    rows = np.arange(chunk)[None, :] < np.asarray(valid)[:, None]  # rows whose logits count
    np.testing.assert_allclose(np.asarray(logits)[rows], np.asarray(want_logits)[rows],
                               atol=1e-4, rtol=1e-4)
    got_l, tree = jax.tree_util.tree_flatten_with_path(new)
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(new)
    for (path, g), w, b in zip(got_l, jax.tree_util.tree_leaves(want),
                               jax.tree_util.tree_leaves(caches)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-4)
        # the idle slot's rows and state are left exactly as they were
        slot_axis = 1 if path[0].key == "scan" else 0
        np.testing.assert_array_equal(np.take(np.asarray(g), 2, axis=slot_axis),
                                      np.take(np.asarray(b), 2, axis=slot_axis))


def test_short_chunk_rows_leave_the_cache_as_it_was(pc8, mesh8):
    """Rows past q_valid write nothing: on a ring whose slots the chunk's
    rows wrap onto, only the live rows' slots change, bit for bit."""
    cfg, params = _model("gemma3-27b", pc8, mesh8, local_window=4, n_layers=6)
    n_slots, chunk, max_len = 2, 4, 24
    caches = _filled_caches(cfg, pc8, n_slots, max_len, seed=3)
    lens = jnp.asarray([6, 11], jnp.int32)
    valid = jnp.asarray([1, 3], jnp.int32)
    tokens = jnp.ones((n_slots, chunk), jnp.int32)
    _, new = jax.jit(lambda p, c, t, n, v: lm.decode_step(p, c, cfg, pc8, t, n, q_valid=v))(
        params, caches, tokens, lens, valid)
    unit, _ = lm.layer_plan(cfg)[1:3]
    for i, d in enumerate(unit):
        old, got = caches["scan"][i]["k"], new["scan"][i]["k"]  # [layers, B, kv, L, hd]
        size = old.shape[3]
        for b in range(n_slots):
            live = {(int(lens[b]) + t) % size if d.window else int(lens[b]) + t
                    for t in range(int(valid[b]))}
            for r in range(size):
                same = np.array_equal(np.asarray(got[:, b, :, r]), np.asarray(old[:, b, :, r]))
                assert same != (r in live), (d.kind, b, r)
