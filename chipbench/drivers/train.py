"""Training through ``make_train_step``: one compiled step with its state,
driven from the seed.

Set-up builds the step and its state and runs the first three steps through
the same call and feed as the window; those three are what the check
compares with the reference's three: each step's loss, each leaf's norm of
the first clipped gradient (read from the optimizer's first moment after step
one) and of the parameters' change after step three.  The window then goes on
from step four with new rows every step.
"""
from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic, weights


def _named(tree) -> dict:
    return {weights.path_name(p): v for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def feed(ctx, index):
    """Rows of step ``index`` (0-based): inputs and next-token labels."""
    mix = ctx.mix
    rows = traffic.token_rows(ctx.seed, index, mix["batch"], mix["seq"] + 1,
                              ctx.arch.vocab_size)
    return {"inputs": rows[:, :-1], "labels": rows[:, 1:]}


def run(ctx):
    from repro.models import lm
    from repro.parallel.context import ParallelContext
    from repro.training import AdamWConfig, init_opt_state, make_train_step

    mix, cfg = ctx.mix, ctx.arch
    opt = mix["optimizer"]
    pc = ParallelContext(mesh=ctx.mesh(), mode="overlap")
    params = ctx.weights(pc)
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    state = jax.jit(init_opt_state)(params)
    step = make_train_step(lm, cfg, pc, AdamWConfig(**opt), remat_policy=mix["remat"],
                           grad_masks=lm.grad_masks(cfg, pc))
    reference = ctx.reference
    norms = jax.jit(reference.leaf_norms)

    losses, grad1 = [], None
    for i in range(3):
        params, state, m = step(params, state, feed(ctx, i))
        losses.append(float(m["loss"]))
        if i == 0:
            grad1 = {k: np.asarray(v) / (1 - opt["b1"])
                     for k, v in jax.device_get(norms(_named(state["mu"]))).items()}
    delta = jax.jit(lambda a, b: reference.leaf_norms(
        {k: a[k] - b[k] for k in a}))(_named(params), _named(p0))
    update3 = {k: np.asarray(v) for k, v in jax.device_get(delta).items()}
    del p0

    tokens = mix["batch"] * mix["seq"]
    done, bad, index, pending = 0, 0, 3, None
    t_open = ctx.open_window()
    while True:
        t = ctx.now()
        ctx.tick(t)
        if t - t_open >= ctx.seconds:
            break
        batch = feed(ctx, index)
        index += 1
        with ctx.span("train_step"):
            params, state, m = step(params, state, batch)
        if pending is not None:
            with ctx.span("fetch"):
                bad += not math.isfinite(float(pending))
            done += 1
        pending = m["loss"]
    if pending is not None:
        bad += not math.isfinite(float(pending))
        done += 1
    ctx.close_window()
    ctx.read_memory()
    del params, state, step, m
    gc.collect()

    _check(ctx, losses, grad1, update3)
    return {"attempted": done, "failed": bad,
            "train": {"steps": done, "tokens": done * tokens, "batch": mix["batch"],
                      "seq": mix["seq"]}}


def leaf_gap(prog: dict, ref: dict, grad_ref: dict) -> float:
    """Worst leaf's gap between the program's norm and the reference's, over
    the larger of that leaf's reference norm and the median leaf's.  Leaves
    whose reference gradient is under a thousandth of the median leaf's
    gradient are left out: they move by round-off alone."""
    names = sorted(ref)
    r = np.concatenate([np.ravel(ref[k]) for k in names]).astype(np.float64)
    p = np.concatenate([np.ravel(prog[k]) for k in names]).astype(np.float64)
    g = np.concatenate([np.ravel(grad_ref[k]) for k in names]).astype(np.float64)
    keep = g >= 1e-3 * np.median(g)
    scale = np.maximum(r, np.median(r[keep]))
    return float(np.max(np.abs(p - r)[keep] / scale[keep]))


def readings(losses, grad1, update3, ref) -> dict:
    loss = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["loss"]))
    return {"loss_gap": loss,
            "grad1_gap": leaf_gap(grad1, ref["grad1"], ref["grad1"]),
            "update3_gap": leaf_gap(update3, ref["update3"], ref["grad1"])}


def _check(ctx, losses, grad1, update3):
    reference = ctx.reference
    key = weights.root_key(ctx.seed)
    kw = reference.options(ctx.cell.config)
    batches = [feed(ctx, i) for i in range(3)]
    ref = reference.train_readings(key, ctx.dims, batches, ctx.mix["optimizer"], **kw)
    got = readings(losses, grad1, update3, ref)
    for name, value in got.items():
        ctx.check(name, value, ctx.mix["check"][name])
    if ctx.control:
        for tag, kw2 in (("bf16", dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)),
                         ("half_batch", dict(fault="half_batch"))):
            low = reference.train_readings(key, ctx.dims, batches, ctx.mix["optimizer"],
                                           **kw2, **kw)
            for name, value in readings(low["loss"], low["grad1"], low["update3"],
                                        ref).items():
                ctx.control_readings[f"{tag}.{name}"] = value
