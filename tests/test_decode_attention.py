"""The decode-attention kernel (``kernels/decode_attention.py``), run in the
interpreter: it reads only the live spans of the slots that decode and
matches plain attention over those rows; merged with the row's own key in
``attention.apply_decode`` it matches XLA's path; and an engine whose decode
passes take it emits the tokens that XLA's path does.

The kernel path is forced on the emulated target by replacing
``attention.decode_kernel_takes``, the function that chooses it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.pallas.mosaic.interpret import interpret_pallas_call
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.configs import get_config
from repro.kernels.decode_attention import NEG_INF, decode_attention, span_rows
from repro.models import lm
from repro.nn import attention
from repro.parallel.context import ParallelContext
from repro.parallel.sharding import place
from repro.serving import ServeEngine
from utils import reduce_config


def _on_chip_rule(c, size, window):
    """The kernel's rule with the target left out."""
    return c == 1 and not attention.is_ring(size, window) and size % 128 == 0


@pytest.fixture
def forced(monkeypatch):
    monkeypatch.setattr(attention, "decode_kernel_takes", _on_chip_rule)


def _plain(q, k, v, lo, hi):
    """Softmax attention in float64 over rows [lo, hi) of each slot; q
    [B, G, R, hd], k/v [B, G, S, hd]."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros(q.shape)
    for b in range(q.shape[0]):
        if hi[b] <= lo[b]:
            continue
        s = np.einsum("grd,gkd->grk", q[b], k[b, :, lo[b]:hi[b]])
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = np.einsum("grk,gkd->grd", p / p.sum(-1, keepdims=True), v[b, :, lo[b]:hi[b]])
    return out


# slots' live rows [lo, hi) in caches of 1536 rows, three spans of 512:
# empty, one row, exactly a span, a span and one, all but the last row, a
# window's rows that start past the first span, an idle slot (hi 0), and a
# range that ends before it starts, inside one span
LO = (0, 0, 0, 0, 0, 600, 0, 700)
HI = (0, 1, 512, 513, 1535, 1100, 0, 650)
KERNEL_CASES = {
    # name: (KV heads G, query heads a KV head R, head dim, cache dtype, span rows)
    "f32_gqa": (2, 3, 64, jnp.float32, 512),
    "bf16_gqa": (2, 3, 64, jnp.bfloat16, 512),
    "f32_mha_wide_span": (1, 1, 32, jnp.float32, 512),
    # 16 heads of 128: a double-buffered span fits the kernel's buffer at 256
    "f32_many_heads": (16, 1, 128, jnp.float32, 256),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_reads_live_rows(case):
    """(acc, m, l) over each slot's live rows against plain attention; a
    slot with no live row returns the empty state, and the layer index
    picks its layer out of the stack.  The kernel runs in the TPU
    interpreter, which performs each copy at its wait and checks the
    double-buffered copies for races."""
    g, r, hd, dtype, span = KERNEL_CASES[case]
    n_layers, b, size = 3, len(HI), 1536
    assert span_rows(size, g, hd, jnp.dtype(dtype).itemsize) == span
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, g, r, hd), jnp.float32) * hd ** -0.5
    k = jax.random.normal(ks[1], (n_layers, b, g, size, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (n_layers, b, g, size, hd)).astype(dtype)
    lo, hi = np.asarray(LO, np.int32), np.asarray(HI, np.int32)
    pltpu.reset_tpu_interpret_mode_state()
    acc, m, l = jax.block_until_ready(decode_attention(
        q, jnp.swapaxes(k, -1, -2), jnp.swapaxes(v, -1, -2), 1, lo, hi,
        interpret=pltpu.InterpretParams(detect_races=True)))
    races = interpret_pallas_call.races
    assert races is not None and not races.races_found
    want = _plain(q, k[1].astype(jnp.float32), v[1].astype(jnp.float32), lo, hi)
    live = hi > lo
    got = np.asarray(acc) / np.where(live[:, None, None, None], np.asarray(l), 1)
    np.testing.assert_allclose(got[live], want[live], atol=2e-6, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(acc)[~live], 0)
    np.testing.assert_array_equal(np.asarray(l)[~live], 0)
    np.testing.assert_array_equal(np.asarray(m)[~live], np.float32(NEG_INF))


# ---- merged with the row's own key, inside apply_decode ----------------------

LAYER_CASES = {
    # name: (cache dtype, window, lengths, q_valid): kv_loc 4 and rep 2
    "f32": (jnp.float32, None, (0, 1, 128, 129, 255, 40), (1, 1, 1, 0, 1, 1)),
    "bf16": (jnp.bfloat16, None, (5, 128, 0, 200, 255, 17), (1, 0, 1, 1, 1, 1)),
    # a windowed layer whose cache is no ring (256 rows, window 300): the
    # window moves the first row of the slots past row 300
    "window": (jnp.float32, 300, (0, 90, 200, 255, 129, 3), (1, 1, 1, 1, 0, 1)),
}


@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_apply_decode_kernel_matches_xla(case, monkeypatch):
    """One decode row a slot through an attention layer from a stacked cache
    of noise: the kernel path's output and rows against XLA's path, on the
    slots with a valid row."""
    dtype, window, lens, valid = LAYER_CASES[case]
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    pc = ParallelContext(mesh=mesh, mode="overlap")
    cfg = dataclasses.replace(reduce_config(get_config("qwen2-72b")), vocab_size=128)
    lay = attention._lay(cfg, pc.tp)
    assert lay.kv_loc > 1 and lay.h_loc // lay.kv_loc > 1
    b, size, n_layers = len(lens), 256, 2
    if window is not None:
        lens = tuple(ln + (window - size + 100) * (i % 2) for i, ln in enumerate(lens))
        size = max(lens) + 1 + (-(max(lens) + 1)) % 128
    params = attention.init(jax.random.PRNGKey(0), cfg, pc.tp, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    stack = {n: jax.random.normal(kk, (n_layers, b, lay.kv_loc, size, cfg.hd)).astype(dtype)
             for n, kk in zip("kv", ks[:2])}
    x = jax.random.normal(ks[2], (b, 1, cfg.d_model), jnp.float32)
    lens, valid = jnp.asarray(lens, jnp.int32), jnp.asarray(valid, jnp.int32)
    cs = {n: P(None, None, None, None, None) for n in "kv"}

    def run():
        return jax.jit(pc.smap(
            lambda p_, x_, c_, l_, n_, i_: attention.apply_decode(
                p_, x_, c_, l_, pc, cfg, window=window, q_valid=n_, layer=i_),
            in_specs=(P(), P(), cs, P(), P(), P()),
            out_specs=(P(), P())))(params, x, stack, lens, valid, jnp.int32(1))

    want, want_rows = run()
    monkeypatch.setattr(attention, "decode_kernel_takes", _on_chip_rule)
    got, rows = run()
    keep = np.asarray(valid) > 0
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep],
                               atol=2e-5, rtol=2e-5)
    for n in rows:
        np.testing.assert_array_equal(np.asarray(rows[n]), np.asarray(want_rows[n]))


# ---- through the engine --------------------------------------------------------

def test_engine_greedy_tokens_on_the_kernel_path(forced, pc8, mesh8):
    """Greedy generate with every decode pass on the kernel (TP over 4
    shards, the slots over 2 data shards) gives XLA's path's tokens."""
    cfg = dataclasses.replace(reduce_config(get_config("smollm-360m")), vocab_size=128)
    params = place(lm.init(jax.random.PRNGKey(0), cfg, pc8, jnp.float32), mesh8,
                   lm.specs(cfg, pc8))
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (3, 9), 0,
                                            cfg.vocab_size), np.int32)
    eng = ServeEngine(cfg, pc8, params, max_len=256, n_slots=4)
    out = eng.generate(prompts, max_new_tokens=7)
    assert eng.stats["kv_rows_read"] < eng.stats["kv_rows_full"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "decode_kernel_takes",
                   lambda c, size, window: False)
        ref = ServeEngine(cfg, pc8, params, max_len=256, n_slots=4).generate(
            prompts, max_new_tokens=7)
    np.testing.assert_array_equal(out, ref)
