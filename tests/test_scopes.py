"""The program names its device work: scopes in the serving step, the train
step, the model's TP forward and every overlap op, and a name for each Pallas
kernel, as the lowered program carries them into the compiled program's
``op_name`` metadata and so into the profiler's trace."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import kernels
from repro.compat import make_mesh, shard_map
from repro.configs import get_config
from repro.core.channels import BlockChannel
from repro.core.compiler import KINDS, SEQ_KINDS, compile_overlap, scope_name
from repro.models import lm
from repro.nn.moe import moe_router
from repro.parallel.context import ParallelContext
from repro.parallel.sharding import place
from repro.serving import Request, ServeEngine
from repro.training import AdamWConfig, init_opt_state, make_train_step
from utils import reduce_config

KEY = jax.random.PRNGKey(0)


def scopes(lowered) -> set:
    """Every scope of every operation's name stack in a lowered program, with
    transforms unwrapped (``transpose(jvp(attn))`` counts as ``attn``) and
    jitted functions (``jit(matmul)``) left out."""
    out = set()
    for loc in re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)):
        if "/" not in loc or loc.startswith("/"):  # a function's or a file's name
            continue
        for entry in loc.split("/"):
            while (m := re.fullmatch(r"(\w+)\((.*)\)", entry)) and m.group(1) != "jit":
                entry = m.group(2)
            out.add(entry)
    return out


def _small(arch, pc, mesh):
    cfg = dataclasses.replace(reduce_config(get_config(arch)), vocab_size=128)
    params = place(lm.init(KEY, cfg, pc, jnp.float32), mesh, lm.specs(cfg, pc))
    return cfg, params


@pytest.fixture(scope="module")
def serve_scopes(pc8, mesh8):
    cfg, params = _small("smollm-360m", pc8, mesh8)
    eng = ServeEngine(cfg, pc8, params, max_len=32, n_slots=2, decode_block=4)
    eng.submit(Request(tokens=np.arange(5, dtype=np.int32), max_new_tokens=4))
    eng._admit()
    inputs, _, _ = eng._prepare()
    return scopes(eng._step_fn.lower(params, eng.pool.caches,
                                     *(jnp.asarray(a) for a in inputs)))


@pytest.mark.parametrize("scope", ["mixed_pass", "decode_pass", "sample", "embed", "layers",
                                   "attn", "kv_write", "kv_read", "mlp", "final_norm",
                                   "head"])
def test_serve_step_names_its_work(serve_scopes, scope):
    assert scope in serve_scopes


@pytest.fixture(scope="module")
def train_scopes(pc8, mesh8):
    cfg, params = _small("smollm-360m", pc8, mesh8)
    step = make_train_step(lm, cfg, pc8, AdamWConfig(), remat_policy="full",
                           grad_masks=lm.grad_masks(cfg, pc8))
    batch = {"inputs": jnp.zeros((2, 16), jnp.int32), "labels": jnp.zeros((2, 16), jnp.int32)}
    return scopes(step.lower(params, init_opt_state(params), batch))


@pytest.mark.parametrize("scope", ["loss", "optimizer", "embed", "layers", "attn", "mlp",
                                   "final_norm", "head"])
def test_train_step_names_its_work(train_scopes, scope):
    assert scope in train_scopes


def test_layer_scopes_follow_the_layer_kind(pc8, mesh8):
    cfg, params = _small("mamba2-2.7b", pc8, mesh8)
    toks = jnp.zeros((2, 16), jnp.int32)
    got = scopes(jax.jit(lambda p, t: lm.forward(p, cfg, pc8, t)[0]).lower(params, toks))
    assert {"mamba", "layers", "head"} <= got


# ---- the overlap ops, on four virtual devices -------------------------------

@pytest.fixture(scope="module")
def mesh4():
    return make_mesh((1, 1, 4), ("pod", "data", "model"))


@pytest.mark.parametrize("fuse_seams", [False, True])
def test_tp_forward_carries_overlap_scopes(mesh4, fuse_seams):
    """The TP=4 forward of a dense GQA model: every collective runs inside
    an ``overlap.<kind>`` scope."""
    pc = ParallelContext(mesh=mesh4, mode="overlap", fuse_seams=fuse_seams)
    cfg, params = _small("qwen2-72b", pc, mesh4)
    toks = jnp.zeros((1, 64), jnp.int32)
    got = scopes(jax.jit(lambda p, t: lm.forward(p, cfg, pc, t)[0]).lower(params, toks))
    want = {"overlap.ag_matmul", "overlap.matmul_rs"}
    if fuse_seams:
        want.add(scope_name(("matmul_rs", "ag_matmul")))
    assert want <= got


def _op_call(kind):
    """A small shard_map call of one compiled overlap op (or fused sequence)."""
    ch = BlockChannel(axis="model")
    r = 4
    if kind == "ag_matmul":
        args = (jnp.ones((r * 8, 16)), jnp.ones((16, 12)))
        return compile_overlap(kind, ch), args, (P("model", None), P(None, None)), P(None, None)
    if kind == "matmul_rs":
        args = (jnp.ones((r * 8, r * 8)), jnp.ones((r * 8, 16)))
        return (compile_overlap(kind, ch), args, (P(None, "model"), P("model", None)),
                P("model", None))
    if kind == "ag_attention":
        args = (jnp.ones((1, 2, r * 8, 8)), jnp.ones((1, 1, r * 8, 8)), jnp.ones((1, 1, r * 8, 8)))
        spec = P(None, None, "model")
        return compile_overlap(kind, ch, causal=True), args, (spec,) * 3, spec
    if kind == ("matmul_rs", "ag_matmul"):
        args = (jnp.ones((r * 8, r * 8)), jnp.ones((r * 8, 16)), jnp.ones((16, r * 4)))
        fn = compile_overlap(list(kind), channel=ch)
        return (lambda x, w1, w2: fn(x, w1, w2)[1], args,
                (P(None, "model"), P("model", None), P(None, "model")), P(None, "model"))
    # the MoE ops: tokens routed to 8 experts, sharded over the axis
    e, d, f = 8, 16, 16
    wr = jax.random.normal(KEY, (d, e))
    g = (compile_overlap(kind, ch, capacity_factor=8.0) if kind == "ag_moe"
         else compile_overlap(list(kind), channel=ch, capacity_factor=8.0))

    def moe(xs, wgu, wdn):
        ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=2)
        return g(xs, ids, wts, wgu, wdn)

    args = (jnp.ones((r * 16, d)), jnp.ones((e, d, 2 * f)), jnp.ones((e, f, d)))
    return (moe, args, (P("model", None), P("model", None, None), P("model", None, None)),
            P("model", None))


@pytest.mark.parametrize("kind", list(KINDS) + list(SEQ_KINDS), ids=str)
def test_each_overlap_op_runs_under_its_scope(kind):
    mesh = make_mesh((4,), ("model",))
    fn, args, in_specs, out_spec = _op_call(kind)
    sm = shard_map(fn, mesh, in_specs=in_specs, out_specs=out_spec)
    assert scope_name(kind) in scopes(jax.jit(sm).lower(*args))


# ---- Pallas kernels, lowered in interpret mode ------------------------------

def _kernel_call(name):
    x, w = jnp.ones((128, 128)), jnp.ones((128, 128))
    if name == "matmul":
        return lambda: kernels.matmul(x, w, interpret=True)
    if name == "flash_attention":
        q = jnp.ones((2, 128, 64))
        return lambda: kernels.flash_attention(q, q, q, causal=True, interpret=True)
    if name == "grouped_matmul":
        experts = jnp.zeros((2,), jnp.int32)
        return lambda: kernels.grouped_matmul(x, jnp.ones((2, 128, 128)), experts,
                                              tile=(64, 128, 128), interpret=True)
    if name == "mamba_ssd":
        return lambda: kernels.ssd_intra_chunk(jnp.zeros((2, 32)), jnp.ones((2, 32, 32)),
                                               jnp.ones((2, 32, 16)), interpret=True)
    if name == "decode_attention":
        q, kv = jnp.ones((2, 1, 1, 64)), jnp.ones((1, 2, 1, 64, 128))
        return lambda: kernels.decode_attention(q, kv, kv, 0, jnp.zeros((2,), jnp.int32),
                                                jnp.asarray([3, 0], jnp.int32),
                                                interpret=True)
    mesh = make_mesh((4,), ("model",))
    if name == "ag_gemm":
        fn = shard_map(lambda a, b: kernels.ag_gemm_shard(a, b, world_size=4, bn=128,
                                                          interpret=True),
                       mesh, in_specs=(P("model", None), P(None, "model")),
                       out_specs=P(None, "model"))
        return lambda: fn(jnp.ones((128, 64)), jnp.ones((64, 512)))
    fn = shard_map(lambda a, b: kernels.gemm_rs_shard(a, b, world_size=4, bn=128,
                                                      interpret=True),
                   mesh, in_specs=(P(None, "model"), P("model", None)),
                   out_specs=P("model", None))
    return lambda: fn(jnp.ones((128, 256)), jnp.ones((256, 256)))


@pytest.mark.parametrize("name", ["ag_gemm", "gemm_rs", "flash_attention", "matmul",
                                  "grouped_matmul", "mamba_ssd", "decode_attention"])
def test_pallas_kernel_carries_its_name(name):
    assert name in scopes(jax.jit(_kernel_call(name)).lower())
