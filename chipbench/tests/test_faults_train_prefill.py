"""The training and TP prefill checks catch the faults those cells can have:
each is planted under the timed path and ``correct`` must come out false."""
import jax
import jax.numpy as jnp

from chipbench.tests import small


def test_train_step_returns_state_unchanged(monkeypatch):
    import repro.training.steps as steps

    monkeypatch.setattr(steps, "apply_update", lambda p, g, s, cfg, grad_masks=None: (
        p, s, {"grad_norm": jnp.float32(0), "lr": jnp.float32(0)}))
    line, _ = small.run("smollm-360m.train")
    assert not line["correct"]
    assert line["checks"]["update3_gap"]["value"] > 0.5


def test_train_half_the_batch_left_out(monkeypatch):
    import repro.training.steps as steps

    real = steps.softmax_xent

    def half(logits, labels, mask=None):
        n = logits.shape[0] // 2
        return real(logits[:n], labels[:n])

    monkeypatch.setattr(steps, "softmax_xent", half)
    line, _ = small.run("smollm-360m.train")
    assert not line["correct"]


def test_prefill_exchange_left_out(monkeypatch):
    from repro.parallel.context import ParallelContext

    def local_only(self, x, w, **kw):
        # the row-parallel product without its reduce-scatter: this rank's
        # partial sums of its own rows
        y = jnp.einsum("bsk,kd->bsd", x, w)
        s_loc = y.shape[1] // self.tp
        return jax.lax.dynamic_slice_in_dim(y, self.axis_index() * s_loc, s_loc, axis=1)

    monkeypatch.setattr(ParallelContext, "matmul_rs", local_only)
    line, _ = small.run("qwen2-72b.prefill-tp4")
    assert not line["correct"]


def test_prefill_answer_altered(monkeypatch):
    from repro.models import lm

    real = lm.forward

    def altered(params, cfg, pc, tokens, **kw):
        logits, aux = real(params, cfg, pc, tokens, **kw)
        return logits.at[:, -1, 0].add(1.0), aux

    monkeypatch.setattr(lm, "forward", altered)
    line, _ = small.run("qwen2-72b.prefill-tp4")
    assert not line["correct"]
