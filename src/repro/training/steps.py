"""Train / eval step builders (jit-compiled, mesh-aware)."""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.training.optimizer import AdamWConfig, apply_update

__all__ = ["softmax_xent", "make_train_step", "make_eval_step"]


def softmax_xent(logits, labels, mask=None):
    """Mean cross-entropy. logits [B,S,V] (any dtype), labels [B,S] int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        m = mask.astype(jnp.float32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return nll.mean()


def make_train_step(model, cfg, pc, opt_cfg: AdamWConfig, *,
                    remat_policy: str = "dots",
                    grad_masks=None,
                    aux_weight: float = 0.01,
                    donate: bool = True,
                    sync_kv: bool = True) -> Callable:
    """Returns jit'd train_step(params, opt_state, batch) -> (params, opt, metrics).

    batch: {"inputs": [B,S] i32, "labels": [B,S] i32, optional "embeds",
    optional "mask"}.
    """

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            logits, aux = model.forward(
                p, cfg, pc, batch["inputs"], embeds=batch.get("embeds"),
                remat_policy=remat_policy)
            ce = softmax_xent(logits, batch["labels"], batch.get("mask"))
            return ce + aux_weight * aux, (ce, aux)

        with jax.named_scope("loss"):
            (loss, (ce, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        if sync_kv and hasattr(model, "sync_grads"):
            with jax.named_scope("grad_sync"):
                grads = model.sync_grads(grads, cfg, pc)
        with jax.named_scope("optimizer"):
            new_params, new_opt, om = apply_update(
                params, grads, opt_state, opt_cfg, grad_masks=grad_masks)
        metrics = {"loss": loss, "ce": ce, "aux": aux, **om}
        return new_params, new_opt, metrics

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(train_step, donate_argnums=donate_argnums)


def make_eval_step(model, cfg, pc) -> Callable:
    def eval_step(params, batch):
        logits, _ = model.forward(params, cfg, pc, batch["inputs"],
                                  embeds=batch.get("embeds"))
        return softmax_xent(logits, batch["labels"], batch.get("mask"))

    return jax.jit(eval_step)
