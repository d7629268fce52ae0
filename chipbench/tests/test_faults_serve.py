"""The serving check catches the faults a serving cell can have: each is
planted under the timed path, the run otherwise goes as on the chip, and
``correct`` must come out false."""
import jax.numpy as jnp
import pytest

from chipbench.tests import small

CELL = "smollm-360m.chat"


def _decode_step_with(monkeypatch, change):
    from repro.models import lm

    real = lm.decode_step

    def broken(params, caches, cfg, pc, tokens, cache_len, unroll=False, q_valid=None):
        return change(real, params, caches, cfg, pc, tokens, cache_len, q_valid)

    monkeypatch.setattr(lm, "decode_step", broken)


def test_altered_token(monkeypatch):
    import repro.serving.engine as engine

    real = engine._sample
    monkeypatch.setattr(engine, "_sample", lambda lg, *a: (real(lg, *a) + 1) % lg.shape[-1])
    line, _ = small.run(CELL)
    assert not line["correct"]
    c = line["checks"]["served_token_gap"]
    assert c["value"] > c["limit"]


def test_state_left_unchanged(monkeypatch):
    def keep_cache(real, params, caches, cfg, pc, tokens, cache_len, q_valid):
        logits, _ = real(params, caches, cfg, pc, tokens, cache_len, q_valid=q_valid)
        return logits, caches

    _decode_step_with(monkeypatch, keep_cache)
    line, _ = small.run(CELL)
    assert not line["correct"]


def test_half_the_batch_left_out(monkeypatch):
    def half(real, params, caches, cfg, pc, tokens, cache_len, q_valid):
        n = tokens.shape[0]
        keep = (jnp.arange(n) % 2 == 0).astype(jnp.int32)  # every other slot
        nv = jnp.full((n,), tokens.shape[1], jnp.int32) if q_valid is None else q_valid
        return real(params, caches, cfg, pc, tokens, cache_len, q_valid=nv * keep)

    _decode_step_with(monkeypatch, half)
    line, _ = small.run(CELL)
    assert not line["correct"]


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_sound_runs_pass(seed):
    line, ctx = small.run(CELL, seed=seed)
    assert line["correct"], line["checks"]
