"""Expert-parallel MoE: the overlapped dispatch/combine all-to-all pair.

The tentpole claim of the EP redesign: ``["a2a_dispatch", "combine_rs"]``
compiles through the same plan -> verifier -> executor pipeline as every
other kind, and the overlapped pipeline matches the unfused
``a2a_moe_baseline`` (bulk AllGather + GroupGEMM + ReduceScatter with
identical capacity semantics) across the full CommSpec sweep — including
capacity regimes that force token drops, where the kept/dropped sets must
agree BITWISE, not just within tolerance.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map, make_mesh
from repro.core import BlockChannel, CommSpec, CompSpec, compile_overlap
from repro.core.moe_overlap import a2a_moe, a2a_moe_baseline, moe_router
from repro.parallel.context import ParallelContext
from utils import allclose

KEY = jax.random.PRNGKey(0)
R = 4  # world size of the parity mesh

ORDERS = ("ring", "bidir_ring", "all2all")
CHANNELS = (1, 2, 4)
ACCUMS = ("float32", "bfloat16")
SWEEP = list(itertools.product(ORDERS, CHANNELS, ACCUMS))


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh((R,), ("model",))


def _chan(order, channels, accum):
    return BlockChannel(axis="model", num_channels=channels,
                        comm=CommSpec(order=order),
                        comp=CompSpec(accum_dtype=accum))


def _tol(accum):
    return dict(atol=2e-4, rtol=2e-3) if accum == "float32" else dict(atol=8e-2, rtol=3e-2)


def _operands(m, d=16, f=16, e=8, k_top=2, scale=0.5):
    x = jax.random.normal(KEY, (m, d)) * scale
    wr = jax.random.normal(jax.random.PRNGKey(5), (d, e))
    wgu = jax.random.normal(jax.random.PRNGKey(6), (e, d, 2 * f)) * 0.1
    wdn = jax.random.normal(jax.random.PRNGKey(7), (e, f, d)) * 0.1
    return x, wr, wgu, wdn


def _ep_shard_fn(mesh, ch, wr, e, k_top, overlapped, capacity_factor):
    """EP layout: tokens sequence-sharded, experts sharded over the same axis."""
    fn = compile_overlap(["a2a_dispatch", "combine_rs"], channel=ch,
                         overlapped=overlapped,
                         capacity_factor=capacity_factor)

    def f_(xs, wgu_, wdn_):
        ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=k_top)
        return fn(xs, ids, wts, wgu_, wdn_)

    return shard_map(f_, mesh,
                     in_specs=(P("model", None), P("model", None, None),
                               P("model", None, None)),
                     out_specs=P("model", None))


# ---- parity sweep: the full comm/comp space ---------------------------------

@pytest.mark.parametrize("order,channels,accum", SWEEP)
def test_parity_a2a_moe(mesh4, order, channels, accum):
    e, k_top = 8, 2
    x, wr, wgu, wdn = _operands(R * 16)
    ch = _chan(order, channels, accum)
    y_o = jax.jit(_ep_shard_fn(mesh4, ch, wr, e, k_top, True, 8.0))(x, wgu, wdn)
    y_b = jax.jit(_ep_shard_fn(mesh4, ch, wr, e, k_top, False, 8.0))(x, wgu, wdn)
    allclose(y_o, y_b, **_tol(accum))


# ---- capacity overflow: kept/dropped token sets must match bitwise ----------

@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("channels", (1, 2))
@pytest.mark.parametrize("capacity_factor", (8.0, 1.0, 0.25))
def test_capacity_drop_parity_bitwise(mesh4, order, channels, capacity_factor):
    """Under capacity pressure both paths must drop the SAME tokens: the
    overlapped pipeline and the baseline feed identical per-tile inputs to
    the same dispatch tables, so their float outputs agree bitwise in f32
    (any divergence in the kept set would show as O(1) output error)."""
    e, k_top = 8, 2
    # 32 tokens/rank + experts 0/1 made hot so tight capacities really
    # overflow in every channel split (the per-tile capacity floors at 8
    # rows; uniform routing of 16 tokens/rank would never hit it)
    x, wr, wgu, wdn = _operands(R * 32)
    wr = wr.at[:, :2].add(10.0)
    ch = _chan(order, channels, "float32")
    y_o = jax.jit(_ep_shard_fn(mesh4, ch, wr, e, k_top, True, capacity_factor))(x, wgu, wdn)
    y_b = jax.jit(_ep_shard_fn(mesh4, ch, wr, e, k_top, False, capacity_factor))(x, wgu, wdn)
    np.testing.assert_array_equal(np.asarray(y_o), np.asarray(y_b))
    if capacity_factor < 1.0:
        # sanity: the tight capacity really dropped something (the dropped
        # tokens contribute zeros, so the two regimes must differ)
        y_full = jax.jit(_ep_shard_fn(mesh4, ch, wr, e, k_top, True, 8.0))(x, wgu, wdn)
        assert not np.array_equal(np.asarray(y_o), np.asarray(y_full))


# ---- nn/moe apply_seq: EP opt-in, aux loss under expert padding -------------

def _moe_cfg(num_experts=8):
    from repro.configs import get_config
    from utils import reduce_config

    cfg = reduce_config(get_config("granite-moe-3b-a800m"))
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=num_experts,
                                     num_shared=0))


def _run_moe_layer(pc, cfg, params, x):
    from repro.nn import moe

    specs = moe.specs(cfg, pc.tp, None)
    in_specs = (jax.tree_util.tree_map(
        pc.manual, specs, is_leaf=lambda v: isinstance(v, P)),
        P(None, "model", None))
    sm = pc.smap(lambda p, xx: moe.apply_seq(p, xx, pc, cfg),
                 in_specs, (P(None, "model", None), P()))
    return jax.jit(sm)(params, x)


@pytest.mark.parametrize("num_experts", (8, 6))
def test_nn_moe_ep_path(mesh4, num_experts):
    """moe.apply_seq under ep_axis == the EP baseline — including when the expert
    count pads up to the EP degree (num_experts=6 -> e_pad=8) and the aux
    loss must only see the valid experts."""
    from repro.nn import moe

    cfg = _moe_cfg(num_experts)
    pc = ParallelContext(mesh=mesh4, ep_axis="model")
    pc_b = ParallelContext(mesh=mesh4, ep_axis="model", mode="baseline")
    params = moe.init(jax.random.PRNGKey(0), cfg, pc.tp, jnp.float32)
    x = jax.random.normal(KEY, (1, R * 8, cfg.d_model), jnp.float32)

    y_o, aux_o = _run_moe_layer(pc, cfg, params, x)  # EP on via ep_axis
    y_b, aux_b = _run_moe_layer(pc_b, cfg, params, x)
    allclose(y_o, y_b, **_tol("float32"))
    # routing (and thus the aux loss) is path-independent; under padding the
    # aux must be computed over the valid experts only, and stay finite
    np.testing.assert_allclose(np.asarray(aux_o), np.asarray(aux_b), rtol=1e-6)
    assert np.isfinite(np.asarray(aux_o)).all()

    # the TP double-ring path still works side by side and agrees (no drops
    # at the generous reduced-config capacity)
    y_t, aux_t = _run_moe_layer(dataclasses.replace(pc, ep_axis=None), cfg, params, x)
    allclose(y_t, y_o, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(aux_t), np.asarray(aux_o), rtol=1e-6)


def test_unified_apply_seq_keyword_surface(mesh4):
    """The nn blocks take no policy keywords: the design point (tune, quant)
    and expert parallelism (ep_axis) are the ParallelContext's alone.  The
    keywords left carry behaviour — the fused seam (next_proj, gu, qkv)."""
    import inspect

    from repro.nn import attention, ffn, moe

    def keywords(fn):
        return {n for n, p in inspect.signature(fn).parameters.items()
                if p.kind is p.KEYWORD_ONLY}

    assert keywords(ffn.apply_seq) == {"gu", "next_proj"}
    assert keywords(moe.apply_seq) == {"next_proj"}
    assert keywords(attention.apply_seq) == {
        "causal", "window", "rope_theta", "attn_chunk", "return_kv", "qkv", "next_proj"}
    assert keywords(attention.apply_seq_ring) == {
        "causal", "window", "rope_theta", "next_proj"}

    cfg = _moe_cfg()
    pc = ParallelContext(mesh=mesh4)  # no ep_axis: EP not opted in
    x = jnp.zeros((1, R * 8, cfg.d_model), jnp.float32)

    for fn in (moe.apply_seq, ffn.apply_seq, attention.apply_seq,
               attention.apply_seq_ring):
        for policy in ("tune", "quant", "ep"):
            with pytest.raises(TypeError, match=policy):
                fn({}, x, pc, cfg, **{policy: True})
    with pytest.raises(ValueError, match="next_proj"):
        moe.apply_seq({}, x, pc, cfg, next_proj=(lambda y: y, None))
    with pytest.raises(ValueError, match="not a mesh axis"):
        ParallelContext(mesh=mesh4, ep_axis="experts")
    with pytest.raises(ValueError, match="ep_axis"):
        ParallelContext(mesh=mesh4).a2a_moe(x, x, x, x, x)


# ---- verifier: exchange legality, seam composition, protocol ---------------

def test_a2a_candidate_space_including_non_power_of_2():
    """Every (order, world, C) point the tuner would consider is legal —
    including world=3, where the all2all order falls back from XOR pairing
    to rotation peers (the non-power-of-2 fallback)."""
    from repro.analysis import check_a2a_candidate

    for order in ORDERS:
        for world in (2, 3, 4, 8):
            for nch in (1, 2, 4):
                assert check_a2a_candidate(order, world, nch) is None, (
                    order, world, nch)


def test_a2a_mutation_rejected_by_verifier():
    """A corrupted exchange destination or a mismatched dispatch/combine pair
    must fail verification with the structured check name attached."""
    from repro.analysis import verify_seq_tables
    from repro.analysis.errors import PlanVerificationError
    from repro.analysis.ir import PlanTables
    from repro.core.plan import build_seq_plan

    ch = _chan("all2all", 2, "float32")
    seq = build_seq_plan(("a2a_dispatch", "combine_rs"), (ch, ch), R, 2)
    tables = [PlanTables.from_plan(op) for op in seq.ops]

    # mis-route one exchange destination on the dispatch half
    t = tables[0]
    row = list(list(map(list, c)) for c in t.a2a_dst)
    row[0][1][0] = (row[0][1][0] + 1) % R
    bad = dataclasses.replace(
        t, a2a_dst=tuple(tuple(tuple(r) for r in c) for c in row))
    with pytest.raises(PlanVerificationError) as ei:
        verify_seq_tables([bad, tables[1]])
    assert ei.value.check in ("a2a_exchange_composition", "a2a_involution",
                              "a2a_seed")

    # a combine that disagrees with its dispatch about who sent step s
    ch_ring = _chan("ring", 2, "float32")
    other = build_seq_plan(("a2a_dispatch", "combine_rs"), (ch_ring, ch_ring), R, 2)
    mixed = [PlanTables.from_plan(seq.ops[0]),
             PlanTables.from_plan(other.ops[1])]
    with pytest.raises(PlanVerificationError) as ei:
        verify_seq_tables(mixed)
    assert ei.value.check == "a2a_seam_composition"


def test_verify_cli_covers_a2a_kinds():
    """`verify --all` includes the a2a kinds and the fused pair."""
    from repro.analysis.verify import SEQ_OPS, A2A_SEQ_KIND

    assert SEQ_OPS[A2A_SEQ_KIND] == ("a2a_dispatch", "combine_rs")
    from repro.core.plan import FLOW_OF_KIND

    assert FLOW_OF_KIND["a2a_dispatch"] == "a2a"
    assert FLOW_OF_KIND["combine_rs"] == "a2a_rs"


# ---- compiler: list form, structured errors --------------------------------

def test_compile_overlap_a2a_list_form(mesh4):
    """The list form compiles the pair; pallas and comp= stay structured
    errors like the RS->AG seam (comp= names the channel that replaces it)."""
    with pytest.raises(NotImplementedError, match="a2a_dispatch"):
        compile_overlap(["a2a_dispatch", "combine_rs"], backend="pallas")
    with pytest.raises(NotImplementedError, match="combine_rs', 'a2a_dispatch"):
        compile_overlap(["combine_rs", "a2a_dispatch"])
    with pytest.raises(TypeError, match=r"channel\.with_\(comp="):
        compile_overlap(["a2a_dispatch", "combine_rs"], comp=(8, 8, 8))

    # channel=None compiles with the default channel
    e, k_top = 8, 2
    x, wr, wgu, wdn = _operands(R * 16)
    fn = compile_overlap(["a2a_dispatch", "combine_rs"], capacity_factor=8.0)

    def f_(xs, wgu_, wdn_):
        ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=k_top)
        return fn(xs, ids, wts, wgu_, wdn_)

    sm = shard_map(f_, mesh4,
                   in_specs=(P("model", None), P("model", None, None),
                             P("model", None, None)),
                   out_specs=P("model", None))
    y = jax.jit(sm)(x, wgu, wdn)
    y_b = jax.jit(_ep_shard_fn(mesh4, _chan("ring", 1, "float32"), wr, e,
                               k_top, False, 8.0))(x, wgu, wdn)
    allclose(y, y_b, **_tol("float32"))


def test_compile_overlap_a2a_auto_channel(mesh4):
    """resolve_a2a resolves the pair jointly (model-ranked) per call shape;
    lowered explicitly, it matches the baseline numerically within the
    winner's flow-dtype tolerance.  A string channel is refused by name."""
    from repro import backend, tune

    e, k_top = 8, 2
    x, wr, wgu, wdn = _operands(R * 16)
    with pytest.raises(ValueError, match="resolve_a2a"):
        compile_overlap(["a2a_dispatch", "combine_rs"], channel="auto")

    def f_(xs, wgu_, wdn_):
        ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=k_top)
        args = (xs, ids, wts, wgu_, wdn_)
        fused, ch_d, ch_c = tune.resolve_a2a(
            shapes=[a.shape for a in args], axis="model",
            world=int(backend.axis_size("model")), capacity_factor=8.0)
        fn = compile_overlap([("a2a_dispatch", ch_d), ("combine_rs", ch_c)],
                             overlapped=fused, capacity_factor=8.0)
        return fn(*args)

    sm = shard_map(f_, mesh4,
                   in_specs=(P("model", None), P("model", None, None),
                             P("model", None, None)),
                   out_specs=P("model", None))
    y = jax.jit(sm)(x, wgu, wdn)
    y_b = jax.jit(_ep_shard_fn(mesh4, _chan("ring", 1, "float32"), wr, e,
                               k_top, False, 8.0))(x, wgu, wdn)
    # the joint search may pick a bf16 flow for the combine partials
    allclose(y, y_b, **_tol("bfloat16"))


# ---- tuner: hop counts, signatures, joint resolution ------------------------

def test_order_hops_derived_from_peer_tables():
    """Satellite: all2all hop counts come from schedules.all2all_peer, not
    the old max(1, world/4) guess — and differ from it where it was wrong."""
    from repro.core import schedules
    from repro.tune.cost import _order_hops

    for order in ("ring", "bidir_ring"):
        assert _order_hops(order, 8) == 1.0
    # power-of-2: mean XOR-pair ring distance
    for world in (2, 4, 8):
        total = sum(
            min((schedules.all2all_peer(r, s, world) - r) % world,
                (r - schedules.all2all_peer(r, s, world)) % world)
            for s in range(1, world) for r in range(world))
        assert _order_hops("all2all", world) == max(
            1.0, total / ((world - 1) * world))
    # non-power-of-2 fallback is rotation: neighbors half the time -> the
    # old world/4 heuristic overcharged it
    assert _order_hops("all2all", 3) == 1.0
    assert _order_hops("all2all", 6) != max(1.0, 6 / 4.0)


def test_moe_signature_workload_axes():
    """MoE signatures carry quantized (imbalance, capacity) axes; every
    consumer slices sig[:5] so the axes never break shape unpacking."""
    from repro import tune
    from repro.tune import cost

    shapes = [(64, 16), (64, 2), (64, 2), (8, 16, 32), (8, 16, 16)]
    base = tune.signature("ag_moe", shapes)
    assert len(base) == 5
    sig = tune.signature(tune.A2A_SEQ_KIND, shapes, imbalance=1.6, capacity=21)
    assert sig[:5] == base
    assert sig[5:] == (6, 24)  # 1.6 -> 6 quarter-units; 21 -> 24 rows
    # capacity without imbalance still pins the positional layout
    sig2 = tune.signature("ag_moe", shapes, capacity=40)
    assert sig2[5:] == (4, 40)
    with pytest.raises(ValueError, match="MoE"):
        tune.signature("ag_matmul", [(8, 8), (8, 8)], capacity=8)
    # cost model consumes the extended sigs without unpacking errors, and a
    # tighter capacity never models slower
    cand = tune.Candidate(order="ring", num_channels=1, accum_dtype="float32")
    for kind in ("ag_moe", "a2a_dispatch", "combine_rs"):
        assert cost.predict_cost(kind, sig, R, cand) > 0.0
    loose = tune.signature("ag_moe", shapes, capacity=512)
    tight = tune.signature("ag_moe", shapes, capacity=8)
    assert (cost.predict_cost("ag_moe", tight, R, cand)
            <= cost.predict_cost("ag_moe", loose, R, cand))


def test_resolve_a2a_joint(mesh4):
    """resolve_a2a returns one shared verified channel for both halves, and
    the overlapped program never models slower than the split one."""
    from repro import tune
    from repro.analysis import check_a2a_candidate
    from repro.tune import cost

    shapes = [(64, 16), (64, 2), (64, 2), (8, 16, 32), (8, 16, 16)]
    fused, ch_d, ch_c = tune.resolve_a2a(shapes=shapes, mesh=mesh4,
                                         capacity_factor=1.25)
    assert fused and ch_d is ch_c
    assert check_a2a_candidate(ch_d.comm.order, R, ch_d.num_channels) is None
    sig = tune.signature(tune.A2A_SEQ_KIND, shapes)
    for cand in tune.enumerate_a2a_candidates(sig=sig, world=R):
        assert (cost.predict_a2a_cost(sig, R, cand, fused=True)
                <= cost.predict_a2a_cost(sig, R, cand, fused=False))


# MoE a2a signatures (m_loc, d_model, top_k, e_loc, d_expert) per shard at
# world 4: deepseek-moe-16b routes top-6 of 64 experts, granite-3b-a800m
# top-8 of 40
MOE_SIGS = {
    "small": (32, 16, 2, 2, 8),
    "deepseek-16b": (512, 256, 6, 16, 176),
    "granite-3b": (512, 192, 8, 10, 64),
}


@pytest.mark.parametrize("name", sorted(MOE_SIGS))
def test_fused_a2a_beats_split_pair_modeled(name):
    """On every MoE shape the best fused dispatch/combine beats the best
    split pair on the modeled scale, and resolve_a2a verdicts fused with one
    shared channel for both halves."""
    from repro import tune
    from repro.tune import cost

    sig = MOE_SIGS[name]
    cands = tune.enumerate_a2a_candidates(sig=sig, world=R)
    assert cands

    def best(fused):
        return min(cost.predict_a2a_cost(sig, R, c, fused=fused) for c in cands)

    assert best(True) < best(False)
    fused, ch_d, ch_c = tune.resolve_a2a(sig=sig, world=R)
    assert fused and ch_d == ch_c

