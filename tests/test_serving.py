"""Serving integration: prefill-into-cache + decode == full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.parallel.sharding import place
from repro.serving import ServeEngine
from utils import reduce_config


@pytest.mark.parametrize("arch", ["qwen2-72b", "gemma3-27b", "mamba2-2.7b"])
def test_prefill_decode_matches_forward(arch, pc8, mesh8):
    """Greedy next-token from (prefill + decode) must match teacher-forced
    forward logits at every position."""
    cfg = reduce_config(get_config(arch))
    cfg = dataclasses.replace(cfg, vocab_size=128)
    params = place(lm.init(jax.random.PRNGKey(0), cfg, pc8, jnp.float32),
                   mesh8, lm.specs(cfg, pc8))
    s0, extra = 16, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, s0 + extra), 0,
                              cfg.vocab_size)

    # teacher-forced forward over the whole sequence
    full_logits, _ = jax.jit(lambda p, t: lm.forward(p, cfg, pc8, t))(
        params, toks)

    # prefill on the prefix, then decode the remaining tokens one by one
    logits_p, caches = jax.jit(
        lambda p, t: lm.prefill(p, cfg, pc8, t, max_len=s0 + extra))(
        params, toks[:, :s0])
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(full_logits[:, :s0]),
                               atol=2e-3, rtol=2e-3)

    step = jax.jit(lambda p, c, t, n: lm.decode_step(p, c, cfg, pc8, t, n))
    for i in range(extra):
        logits_d, caches = step(params, caches, toks[:, s0 + i: s0 + i + 1],
                                s0 + i)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]), np.asarray(full_logits[:, s0 + i]),
            atol=2e-3, rtol=2e-3)


def test_sliding_window_ring_cache_decode(pc8, mesh8):
    """gemma3-style local layers with a ring-buffer cache smaller than the
    sequence must match teacher-forced forward logits."""
    cfg = reduce_config(get_config("gemma3-27b"))
    cfg = dataclasses.replace(cfg, vocab_size=128, local_window=8,
                              n_layers=len(cfg.pattern))
    params = place(lm.init(jax.random.PRNGKey(0), cfg, pc8, jnp.float32),
                   mesh8, lm.specs(cfg, pc8))
    s0, extra = 16, 8  # decode well past the window (total % tp == 0)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, s0 + extra), 0,
                              cfg.vocab_size)
    full_logits, _ = jax.jit(lambda p, t: lm.forward(p, cfg, pc8, t))(params, toks)
    logits_p, caches = jax.jit(
        lambda p, t: lm.prefill(p, cfg, pc8, t, max_len=s0 + extra))(
        params, toks[:, :s0])
    step = jax.jit(lambda p, c, t, n: lm.decode_step(p, c, cfg, pc8, t, n))
    for i in range(extra):
        logits_d, caches = step(params, caches, toks[:, s0 + i: s0 + i + 1],
                                s0 + i)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]), np.asarray(full_logits[:, s0 + i]),
            atol=2e-3, rtol=2e-3)


def test_serve_engine_generates(pc8, mesh8):
    cfg = reduce_config(get_config("smollm-360m"))
    cfg = dataclasses.replace(cfg, vocab_size=128)
    params = place(lm.init(jax.random.PRNGKey(0), cfg, pc8, jnp.float32),
                   mesh8, lm.specs(cfg, pc8))
    eng = ServeEngine(cfg, pc8, params, max_len=48)
    prompts = np.ones((2, 8), np.int32)
    out = eng.generate(prompts, max_new_tokens=8)
    assert out.shape == (2, 16)
    # deterministic greedy decode
    out2 = eng.generate(prompts, max_new_tokens=8)
    np.testing.assert_array_equal(out, out2)


# ---- request-level continuous-batching engine -------------------------------

def _build(arch, pc, mesh, vocab=128, **over):
    cfg = reduce_config(get_config(arch))
    cfg = dataclasses.replace(cfg, vocab_size=vocab, **over)
    params = place(lm.init(jax.random.PRNGKey(0), cfg, pc, jnp.float32),
                   mesh, lm.specs(cfg, pc))
    return cfg, params


def _ref_greedy(cfg, pc, params, prompts, n_new, max_len):
    """Old ServeEngine semantics: per-token host round-trip greedy loop.

    The pinned reference the request-level engine must reproduce exactly
    under greedy sampling.  Feeds the prompt token by token (works for any
    prompt length — lm.prefill seq-shards over the TP axis, so it would
    need length % tp == 0; prefill==tokenwise parity is pinned separately
    by test_prefill_decode_matches_forward)."""
    prompts = np.asarray(prompts, np.int32)
    b, s0 = prompts.shape
    caches = lm.init_caches(cfg, pc, b, max_len, jnp.float32)
    step = jax.jit(lambda p, c, t, n: lm.decode_step(p, c, cfg, pc, t, n))
    lg = None
    for t in range(s0):
        lg, caches = step(params, caches, jnp.asarray(prompts[:, t:t + 1]), t)
    out = [np.asarray(jnp.argmax(lg[:, 0], -1).astype(jnp.int32))]
    for i in range(n_new - 1):
        lg, caches = step(params, caches, jnp.asarray(out[-1])[:, None], s0 + i)
        out.append(np.asarray(jnp.argmax(lg[:, 0], -1).astype(jnp.int32)))
    return np.stack(out, axis=1)  # [B, n_new]


def test_generate_parity_old_vs_new(pc8, mesh8):
    """generate() (submit/step/drain underneath) == the old fixed-batch
    prefill + per-token greedy loop, token for token (satellite)."""
    cfg, params = _build("smollm-360m", pc8, mesh8)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (2, 8), 0, cfg.vocab_size), np.int32)
    eng = ServeEngine(cfg, pc8, params, max_len=48)
    out = eng.generate(prompts, max_new_tokens=6)
    ref = _ref_greedy(cfg, pc8, params, prompts, 6, max_len=48)
    np.testing.assert_array_equal(out[:, 8:], ref)


def test_step_host_sync_and_trace_counts(pc8, mesh8):
    """The jit'd step is the no-per-token-round-trip contract: one trace
    total, one host sync per step, many tokens per sync — with requests
    admitted mid-run as slots free up (tentpole acceptance)."""
    from repro.serving import Request

    cfg, params = _build("smollm-360m", pc8, mesh8)
    eng = ServeEngine(cfg, pc8, params, max_len=64, n_slots=2, decode_block=8)
    key = jax.random.PRNGKey(7)
    prompts = [np.asarray(jax.random.randint(key, (ln,), 0, cfg.vocab_size),
                          np.int32) for key, ln in
               zip(jax.random.split(key, 3), (5, 13, 9))]
    budgets = (4, 10, 6)
    hs = [eng.submit(Request(tokens=p, max_new_tokens=b))
          for p, b in zip(prompts, budgets)]
    # only 2 slots: the third request must wait in the queue
    assert eng.poll(hs[2])["queued"]
    outs = eng.drain(hs)
    assert eng.stats["steps"] >= 2  # mid-run admission forced extra steps
    assert eng.stats["host_syncs"] == eng.stats["steps"]
    assert eng.stats["step_traces"] == 1  # static shapes: one trace, ever
    # decode ran in blocks: some step emitted >1 token for one sync
    assert max(len(o) for o in outs.values()) > eng.stats["steps"] >= 1
    for h, p, b in zip(hs, prompts, budgets):
        assert eng.poll(h)["done"]
        ref = _ref_greedy(cfg, pc8, params, p[None, :], b, max_len=64)
        np.testing.assert_array_equal(outs[h], ref[0])


@pytest.mark.parametrize("n_requests", [1, 3])
def test_step_counts_decode_passes(pc8, mesh8, n_requests):
    """A batch seated together decodes in lockstep: each step's mixed pass
    gives every request its first token of the step, and the engine's
    ``passes`` counts the decode passes that gave the rest."""
    from repro.serving import Request

    cfg, params = _build("smollm-360m", pc8, mesh8)
    eng = ServeEngine(cfg, pc8, params, max_len=64, n_slots=4, decode_block=4)
    prompt = np.arange(6, dtype=np.int32)
    hs = [eng.submit(Request(tokens=prompt, max_new_tokens=11)) for _ in range(n_requests)]
    firsts = 0  # steps in which the batch received tokens
    while not all(eng.poll(h)["done"] for h in hs):
        firsts += bool(eng.step())
    st = eng.stats
    assert st["passes"] == 11 - firsts > 0
    assert st["decode_tokens"] == 11 * n_requests
    assert st["prefill_tokens"] == len(prompt) * n_requests
    assert st["admitted"] == n_requests
    assert st["host_syncs"] == st["steps"]


def test_exact_token_count_and_eos(pc8, mesh8):
    """Exactly max_new_tokens tokens unless eos arrives first; eos stops the
    slot early and is included in the output (bugfix satellite)."""
    from repro.serving import Request

    cfg, params = _build("smollm-360m", pc8, mesh8)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (2, 6), 0, cfg.vocab_size), np.int32)
    ref = _ref_greedy(cfg, pc8, params, prompts, 8, max_len=32)

    # max_new_tokens=1: exactly one token == argmax of the prefill logits
    eng = ServeEngine(cfg, pc8, params, max_len=32, n_slots=2)
    outs = eng.drain([eng.submit(Request(tokens=r, max_new_tokens=1))
                      for r in prompts])
    for h, row in zip(sorted(outs), ref[:, :1]):
        np.testing.assert_array_equal(outs[h], row)

    # eos mid-stream: row 0 stops at the eos position, row 1 (same batch,
    # eos it never emits) runs to its full budget
    eos = int(ref[0, 3])
    eng2 = ServeEngine(cfg, pc8, params, max_len=32, n_slots=2)
    h0 = eng2.submit(Request(tokens=prompts[0], max_new_tokens=8, eos_id=eos))
    h1 = eng2.submit(Request(tokens=prompts[1], max_new_tokens=8,
                             eos_id=cfg.vocab_size + 1))
    outs2 = eng2.drain([h0, h1])
    stop = int(np.argmax(ref[0] == eos))  # first eos occurrence in reference
    np.testing.assert_array_equal(outs2[h0], ref[0, :stop + 1])
    assert outs2[h0][-1] == eos
    np.testing.assert_array_equal(outs2[h1], ref[1])


def test_engine_gqa_and_sampling(pc8, mesh8):
    """GQA config (kv_heads > 1 on tp=4) through the engine; greedy matches
    the reference loop, and seeded sampling is reproducible + composition
    independent (same request alone or sharing the batch)."""
    from repro.serving import Request

    cfg, params = _build("qwen2-72b", pc8, mesh8)
    assert cfg.n_kv_heads > 1
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(11), (2, 8), 0, cfg.vocab_size), np.int32)
    eng = ServeEngine(cfg, pc8, params, max_len=32)
    out = eng.generate(prompts, max_new_tokens=4)
    ref = _ref_greedy(cfg, pc8, params, prompts, 4, max_len=32)
    np.testing.assert_array_equal(out[:, 8:], ref)

    # sampled decode: per-request seed makes results batch-composition
    # independent — alone vs. sharing the batch gives identical tokens
    req = Request(tokens=prompts[0], max_new_tokens=4, temperature=0.7,
                  top_k=8, seed=3)
    alone = ServeEngine(cfg, pc8, params, max_len=32)
    a = alone.drain([alone.submit(req)])
    both = ServeEngine(cfg, pc8, params, max_len=32)
    hs = [both.submit(req),
          both.submit(Request(tokens=prompts[1], max_new_tokens=4,
                              temperature=0.9, seed=12))]
    b = both.drain(hs)
    np.testing.assert_array_equal(list(a.values())[0], b[hs[0]])


def _kv_rows_run(arch, pc, mesh, monkeypatch, kernel):
    """Two requests of 6 tokens through a 1024-row cache, prompts of 9 and
    520 tokens fed 128 a step, with the decode-attention kernel's rule
    forced (its target left out) or never taken.  Returns the stats and
    the number of attention layers."""
    from repro.nn import attention
    from repro.serving import Request

    monkeypatch.setattr(attention, "decode_kernel_takes",
                        lambda c, size, window: (kernel and c == 1 and size % 128 == 0
                                                 and not attention.is_ring(size, window)))
    cfg, params = _build(arch, pc, mesh)
    eng = ServeEngine(cfg, pc, params, max_len=1024, n_slots=2, prefill_chunk=128,
                      decode_block=8)
    prompts = [np.arange(n, dtype=np.int32) % cfg.vocab_size for n in (9, 520)]
    eng.drain([eng.submit(Request(tokens=p, max_new_tokens=6)) for p in prompts])
    assert (eng.stats["steps"], eng.stats["passes"]) == (5, 10)
    return eng.stats, cfg


@pytest.mark.parametrize("kernel", [True, False])
def test_kv_rows_read_counts_live_spans(pc8, mesh8, monkeypatch, kernel):
    """``kv_rows_read`` counts the cache rows the decode passes read, in the
    kernel's spans of 512 rows of a 1024-row cache, against ``kv_rows_full``,
    every row of every slot in every pass; both sum over attention layers,
    and on XLA's path the two agree.

    By hand: step 1 seats both requests; the first finishes its prompt and
    decodes 5 passes over 9..13 cached rows (one span each).  Steps 2-4 feed
    the second's prompt; step 5 finishes it, and its 5 passes read 520..524
    rows (two spans each)."""
    st, cfg = _kv_rows_run("smollm-360m", pc8, mesh8, monkeypatch, kernel)
    layers = cfg.n_layers
    assert st["kv_rows_full"] == 10 * 2 * 1024 * layers
    if kernel:
        assert st["kv_rows_read"] == (5 * 512 + 5 * 2 * 512) * layers < st["kv_rows_full"]
    else:
        assert st["kv_rows_read"] == st["kv_rows_full"]


def test_kv_rows_read_counts_ring_layers_whole(pc8, mesh8, monkeypatch):
    """With sliding-window layers (gemma3: five local layers of window 1024
    to one global, so the local caches are rings of 1024 rows), the kernel
    reads the global layers' live spans as above and XLA every row of the
    rings."""
    st, cfg = _kv_rows_run("gemma3-27b", pc8, mesh8, monkeypatch, True)
    local = sum(cfg.layer_kind(i) == "attn_local" for i in range(cfg.n_layers))
    assert 0 < local < cfg.n_layers
    full = 10 * 2 * 1024
    assert st["kv_rows_full"] == full * cfg.n_layers
    assert st["kv_rows_read"] == full * local + (5 * 512 + 5 * 2 * 512) * (cfg.n_layers - local)
