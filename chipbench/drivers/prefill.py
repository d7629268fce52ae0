"""Closed-loop prompt prefill through the jitted ``lm.forward``: one prompt
per forward, a new seeded prompt each time, logits at every position.

Each forward keeps a few rows of its logits (positions drawn from the seed,
the last among them).  After the window the check runs the reference over
the prompts of a few forwards drawn from the seed, the last one among them,
and compares those rows: the largest difference over the reference rows'
root mean square.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic, weights


def prompt(ctx, index):
    mix = ctx.mix
    return traffic.token_rows(ctx.seed, index, mix["batch"], mix["seq"], ctx.arch.vocab_size)


def positions(ctx, index):
    """Rows of forward ``index`` that the run keeps: the last position and
    others drawn from the seed."""
    seq, k = ctx.mix["seq"], int(ctx.mix["check"]["rows"])
    pick = traffic.rng(ctx.seed, 5, index).choice(seq - 1, k - 1, replace=False)
    return np.sort(np.append(pick, seq - 1)).astype(np.int32)


def run(ctx):
    from repro.models import lm
    from repro.parallel.context import ParallelContext

    mix, cfg = ctx.mix, ctx.arch
    pc = ParallelContext(mesh=ctx.mesh(), mode="overlap")
    params = ctx.weights(pc)
    forward = jax.jit(lambda p, t: lm.forward(p, cfg, pc, t)[0])
    keep = jax.jit(lambda logits, pos: jnp.take(logits, pos, axis=1))

    # warm-up: the window's shapes, once
    jax.block_until_ready(keep(forward(params, prompt(ctx, 0)), positions(ctx, 0)))

    kept = []
    t_open = ctx.open_window()
    index = 1
    while True:
        t = ctx.now()
        ctx.tick(t)
        if t - t_open >= ctx.seconds:
            break
        toks = prompt(ctx, index)
        with ctx.span("forward"):
            rows = keep(forward(params, toks), positions(ctx, index))
            jax.block_until_ready(rows)
        kept.append(rows)
        index += 1
    ctx.close_window()
    ctx.read_memory()
    del params, forward
    gc.collect()

    done = len(kept)
    bad = sum(0 if bool(jnp.isfinite(r).all()) else 1 for r in kept)
    _check(ctx, kept)
    return {"attempted": done, "failed": bad,
            "prefill": {"forwards": done, "tokens": done * mix["batch"] * mix["seq"],
                        "batch": mix["batch"], "seq": mix["seq"]}}


def checked(ctx, done):
    """Window forwards (1-based) the check compares: the last and others
    drawn from the seed."""
    k = min(int(ctx.mix["check"]["forwards"]), done)
    pick = traffic.rng(ctx.seed, 6).permutation(done - 1)[: k - 1] + 1
    return sorted(set(int(i) for i in pick) | {done})


def _check(ctx, kept):
    if not kept:
        ctx.check("no_forward_finished", 1, 0)
        return
    idx = checked(ctx, len(kept))
    reference = ctx.reference
    key = weights.root_key(ctx.seed)
    kw = reference.options(ctx.cell.config)
    toks = np.concatenate([prompt(ctx, i) for i in idx])
    rows = np.concatenate([np.broadcast_to(positions(ctx, i), (ctx.mix["batch"],
                                                               ctx.mix["check"]["rows"]))
                           for i in idx])
    got = np.concatenate([np.asarray(jax.device_get(kept[i - 1]), np.float32) for i in idx])
    with jax.default_device(ctx.devices[0]):
        ref = np.asarray(reference.forward_rows(key, ctx.dims, toks, rows, **kw))
        ctx.check("logit_rel_err", rel_err(got, ref), ctx.mix["check"]["logit_rel_err"])
        if ctx.control:
            low = np.asarray(reference.forward_rows(key, ctx.dims, toks, rows,
                                                    dtype=jnp.bfloat16, **kw))
            ctx.control_readings["logit_rel_err"] = rel_err(low, ref)


def rel_err(got, ref) -> float:
    """Largest difference over the reference rows' root mean square; rows
    that are not finite read 1e30."""
    if not np.isfinite(got).all():
        return 1e30
    return float(np.max(np.abs(got - ref)) / np.sqrt(np.mean(np.square(ref))))

