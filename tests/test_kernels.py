"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import kernels
from repro.compat import shard_map, make_mesh
from repro.core.channels import BlockChannel
from repro.kernels import ref
from utils import allclose

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 128, 384), (384, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, n, k, dtype):
    x = jax.random.normal(KEY, (m, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
    y = kernels.matmul(x, w, interpret=True)
    r = ref.matmul_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    allclose(y.astype(jnp.float32), r.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("gqa", [1, 2])
def test_flash_attention_sweep(causal, window, gqa):
    bh, s, d = 4, 256, 64
    q = jax.random.normal(KEY, (bh, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (bh // gqa, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (bh // gqa, s, d), jnp.float32)
    y = kernels.flash_attention(q, k, v, causal=causal, window=window,
                                bq=128, bk=128, interpret=True)
    r = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    allclose(y, r, atol=2e-4, rtol=2e-3)


def test_flash_attention_comp_tile():
    # the tuner's CompSpec (tm, ., tk) derives (block_q, block_kv); tk=96
    # clamps to the largest divisor of Sk (the shared degrade rule)
    bh, s, d = 2, 256, 64
    q = jax.random.normal(KEY, (bh, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (1, s, d), jnp.float32)
    y = kernels.flash_attention(q, k, v, causal=True, tile=(64, 128, 96),
                                interpret=True)
    r = ref.flash_attention_ref(q, k, v, causal=True)
    allclose(y, r, atol=2e-4, rtol=2e-3)
    # the default sentinel leaves bq/bk untouched (backend-chosen blocking)
    y0 = kernels.flash_attention(q, k, v, causal=True, tile=(128, 128, 128),
                                 interpret=True)
    yn = kernels.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(yn))


def test_grouped_matmul_clamps_non_dividing_tile():
    # tuner-resolved tiles may not divide awkward extents: bn=48 / bk=64
    # clamp via largest_divisor (40, 48) instead of refusing
    e, m, k, n, bm = 4, 256, 96, 80, 64
    tile_expert = jnp.array([0, 1, 3, 3], jnp.int32)
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (e, k, n), jnp.float32)
    y = kernels.grouped_matmul(x, w, tile_expert, tile=(bm, 48, 64),
                               interpret=True)
    r = ref.grouped_matmul_ref(x, w, tile_expert, bm)
    allclose(y, r, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_dynamic_mapping(dtype):
    e, m, k, n, bm = 6, 512, 128, 256, 128
    tile_expert = jnp.array([0, 2, 2, 5], jnp.int32)
    x = jax.random.normal(KEY, (m, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(4), (e, k, n), dtype)
    y = kernels.grouped_matmul(x, w, tile_expert, tile=(bm, 128, 128),
                               interpret=True)
    r = ref.grouped_matmul_ref(x, w, tile_expert, bm)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    allclose(y.astype(jnp.float32), r.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_ssd_chunked_vs_sequential(chunk):
    b, sl, h, p, g, n = 2, 128, 4, 16, 2, 8
    x = jax.random.normal(KEY, (b, sl, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(5), (b, sl, h)))
    a_log = jax.random.normal(jax.random.PRNGKey(6), (h,)) * 0.5
    bm = jax.random.normal(jax.random.PRNGKey(7), (b, sl, g, n)) * 0.3
    cm = jax.random.normal(jax.random.PRNGKey(8), (b, sl, g, n)) * 0.3
    y = kernels.ssd_chunked(x, dt, a_log, bm, cm, chunk=chunk)
    r = ref.ssd_ref(x, dt, a_log, bm, cm)
    allclose(y, r, atol=1e-4, rtol=1e-3)


def test_ssd_chunked_state_continuation():
    """Final state from chunked == final state from sequential recurrence."""
    b, sl, h, p, g, n = 1, 64, 2, 8, 1, 4
    x = jax.random.normal(KEY, (b, sl, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(5), (b, sl, h)))
    a_log = jnp.zeros((h,))
    bm = jax.random.normal(jax.random.PRNGKey(7), (b, sl, g, n)) * 0.3
    cm = jax.random.normal(jax.random.PRNGKey(8), (b, sl, g, n)) * 0.3
    y1, h1 = kernels.ssd_chunked(x, dt, a_log, bm, cm, chunk=16,
                                 return_state=True)
    # continue for one decode step and compare against full-length chunked
    y_full = kernels.ssd_chunked(
        jnp.concatenate([x, x[:, :16]], 1),
        jnp.concatenate([dt, dt[:, :16]], 1), a_log,
        jnp.concatenate([bm, bm[:, :16]], 1),
        jnp.concatenate([cm, cm[:, :16]], 1), chunk=16)
    y2 = kernels.ssd_chunked(x[:, :16], dt[:, :16], a_log, bm[:, :16],
                             cm[:, :16], chunk=16, h_init=h1)
    allclose(y2, y_full[:, sl:], atol=1e-4, rtol=1e-3)


def test_ssd_intra_chunk_kernel():
    t, q, p = 4, 32, 16
    cum = -jnp.abs(jax.random.normal(KEY, (t, q))).cumsum(axis=1)
    cb = jax.random.normal(jax.random.PRNGKey(9), (t, q, q)) * 0.3
    xdt = jax.random.normal(jax.random.PRNGKey(10), (t, q, p)) * 0.5
    y = kernels.ssd_intra_chunk(cum, cb, xdt, interpret=True)
    # oracle
    diff = cum[:, :, None] - cum[:, None, :]
    mask = np.tril(np.ones((q, q), bool))
    g = np.asarray(cb) * np.where(mask, np.exp(np.asarray(diff)), 0.0)
    r = np.einsum("tqk,tkp->tqp", g, np.asarray(xdt))
    allclose(y, r, atol=1e-4, rtol=1e-3)


# ---- fused communication kernels (remote DMA + semaphores, interpret mode) --

def test_ag_gemm_fused_ring():
    mesh = make_mesh((4,), ("model",))
    r, m_loc, k, n_loc = 4, 32, 64, 256
    x = jax.random.normal(KEY, (r * m_loc, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(11), (k, r * n_loc), jnp.float32)
    fn = shard_map(
        lambda a, b: kernels.ag_gemm_shard(a, b, world_size=r, bn=128,
                                           interpret=True),
        mesh, in_specs=(P("model", None), P(None, "model")),
        out_specs=P(None, "model"))
    y = jax.jit(fn)(x, w)
    allclose(y, x @ w, atol=1e-3, rtol=1e-3)


def test_gemm_rs_fused_ring():
    mesh = make_mesh((4,), ("model",))
    m, k_loc, n = 128, 64, 256
    x = jax.random.normal(KEY, (m, 4 * k_loc), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(12), (4 * k_loc, n), jnp.float32)
    fn = shard_map(
        lambda a, b: kernels.gemm_rs_shard(a, b, world_size=4, bn=128,
                                           interpret=True),
        mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P("model", None))
    y = jax.jit(fn)(x, w)
    allclose(y, x @ w, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("kind", ["ag_matmul", "matmul_rs"])
def test_fused_kernels_pad_lane_dims(kind):
    # K and N off the 128 lane grid: the kernels pad to it and drop the pad
    from repro.core.compiler import compile_overlap

    mesh = make_mesh((4,), ("model",))
    fn = compile_overlap(kind, BlockChannel(axis="model"), backend="pallas", world_size=4)
    if kind == "ag_matmul":
        x = jax.random.normal(KEY, (4 * 16, 40), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(13), (40, 4 * 96), jnp.float32)
        specs = dict(in_specs=(P("model", None), P(None, "model")), out_specs=P(None, "model"))
    else:
        x = jax.random.normal(KEY, (4 * 16, 4 * 40), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(13), (4 * 40, 96), jnp.float32)
        specs = dict(in_specs=(P(None, "model"), P("model", None)), out_specs=P("model", None))
    y = jax.jit(shard_map(fn, mesh, **specs))(x, w)
    assert y.shape == (x.shape[0], w.shape[1])
    allclose(y, x @ w, atol=1e-3, rtol=1e-3)


def test_gemm_rs_untileable_channel_width_raises():
    # N=384 in 2 channels gives 192-column channels: no 128-multiple block
    # divides them, so the kernel refuses instead of emitting a block the
    # TPU compiler would reject
    mesh = make_mesh((4,), ("model",))
    fn = shard_map(
        lambda a, b: kernels.gemm_rs_shard(
            a, b, channel=BlockChannel(axis="model", num_channels=2), world_size=4),
        mesh, in_specs=(P(None, "model"), P("model", None)), out_specs=P("model", None))
    x = jnp.ones((64, 4 * 32), jnp.float32)
    w = jnp.ones((4 * 32, 384), jnp.float32)
    with pytest.raises(NotImplementedError, match="N=384 with num_channels=2"):
        jax.jit(fn)(x, w)


def test_gemm_rs_matches_paper_schedule():
    """Segment order must follow the paper's seg=(rank+stage+1)%W ring."""
    from repro.core.schedules import ring_rs_segment
    w = 4
    for rank in range(w):
        segs = [ring_rs_segment(rank, s, w) for s in range(w)]
        assert segs[-1] == rank  # final stage = own segment
        assert sorted(segs) == list(range(w))  # visits every segment once
