"""Bring-up smoke of the system's main paths on TPU v5e, through the entry
points a user calls, at the full published width of smollm-360m
(32 layers, d_model 960, 15 query / 5 KV heads, vocab 49152; random weights
from a seed).

  python chip_smoke.py            one chip: device, serve, train
  python chip_smoke.py --chips 4  four chips only: the TP forward in
                                  mode="overlap" vs mode="baseline", and the
                                  fused Pallas AG+GEMM / GEMM+RS kernels vs
                                  the XLA executor

Every phase asserts what it produced; any failure exits non-zero.  Earlier
lines report set-up and compile seconds and token counts, which are not
speed measurements.  The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
There is no CPU or interpreter fallback: without a TPU the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro import backend  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.channels import BlockChannel  # noqa: E402
from repro.core.compiler import compile_overlap  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.parallel.context import ParallelContext  # noqa: E402
from repro.parallel.sharding import place  # noqa: E402

ARCH = "smollm-360m"
AXES = ("pod", "data", "model")
# TP forward: overlap vs baseline logits, both in f32 matmul precision
# ("highest"), relative to the largest baseline logit
TP_TOL = 1e-3
# fused kernels vs the XLA executor: bf16 operands and outputs, f32
# accumulation in both; the reduction order differs, so a few bf16 ulps
# relative to the largest output
FUSED_TOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


def phase_device(count: int) -> dict:
    """The chip is there and the kernels lower to Mosaic, or exit non-zero."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax platform {dev.platform!r})")
    if backend.target() != "tpu":
        raise SystemExit(
            f"chip_smoke: backend target is {backend.target()!r}; unset REPRO_BACKEND")
    if len(jax.devices()) != count:
        raise SystemExit(f"chip_smoke: need {count} chip(s), JAX sees {len(jax.devices())}")
    log(f"device: {json.dumps(backend.describe())}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def phase_serve(cfg, mesh, *, n_requests=8, prompt_len=512, new_tokens=64, slots=8,
                seed=0) -> dict:
    """Continuous-batching serving through ``repro.launch.serve.serve``."""
    res = serve(cfg, mesh, n_requests=n_requests, prompt_len=prompt_len,
                new_tokens=new_tokens, slots=slots, seed=seed)
    st = res["stats"]
    lens = [len(t) for t in res["tokens"]]
    check(lens == [new_tokens] * n_requests,
          f"serve: token counts {lens}, expected {new_tokens} each")
    check(all(((t >= 0) & (t < cfg.vocab_size)).all() for t in res["tokens"]),
          "serve: a token id is outside the vocabulary")
    check(st["step_traces"] == 1, f"serve: the step traced {st['step_traces']} times")
    check(st["host_syncs"] == st["steps"],
          f"serve: {st['host_syncs']} host syncs over {st['steps']} steps")
    log(f"serve: {n_requests} requests x {prompt_len} prompt + {new_tokens} new tokens, "
        f"{st['steps']} steps, {st['host_syncs']} host syncs, {st['step_traces']} trace; "
        f"setup {res['setup_s']:.1f} s, drain wall {res['drain_s']:.1f} s "
        "(includes the step's compile)")
    return res


def phase_train(cfg, *, steps=3, batch=8, seq=1024) -> list:
    """Training steps through ``repro.launch.train.train`` (no reduction)."""
    hist = []
    t0 = time.perf_counter()
    train(cfg, steps=steps, batch=batch, seq=seq, reduce=False, log_every=1,
          remat_policy="full", on_step=lambda step, m: hist.append(m))
    dt = time.perf_counter() - t0
    check(len(hist) == steps, f"train: {len(hist)} steps ran, expected {steps}")
    for i, m in enumerate(hist):
        check(math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"]),
              f"train: step {i} loss={m['loss']} grad_norm={m['grad_norm']}")
    log(f"train: {steps} steps at batch {batch} x seq {seq}, "
        f"losses {[m['loss'] for m in hist]}, grad norms {[m['grad_norm'] for m in hist]}; "
        f"wall {dt:.1f} s (includes set-up and compile)")
    return hist


def _rel_err(a, b):
    """max|a - b| / max|b|, computed on the device."""
    d = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
    return float(d / jnp.maximum(jnp.max(jnp.abs(b.astype(jnp.float32))), 1e-30))


def phase_tp(cfg, mesh, *, batch=8, seq=2048, seed=0) -> float:
    """``lm.forward`` over the TP axis: overlap (the ``run_plan`` ppermute
    executor) vs baseline (bulk collectives)."""
    pc_o = ParallelContext(mesh=mesh, mode="overlap")
    pc_b = ParallelContext(mesh=mesh, mode="baseline")
    params = place(lm.init(jax.random.PRNGKey(seed), cfg, pc_o, jnp.float32),
                   mesh, lm.specs(cfg, pc_o))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        lo = jax.jit(lambda p, t: lm.forward(p, cfg, pc_o, t)[0])(params, toks)
        lb = jax.jit(lambda p, t: lm.forward(p, cfg, pc_b, t)[0])(params, toks)
    check(lo.shape == lb.shape and lo.shape[:2] == (batch, seq),
          f"tp: logits shapes {lo.shape} vs {lb.shape}")
    check(bool(jnp.isfinite(lo).all()), "tp: overlap logits are not finite")
    err = _rel_err(lo, lb)
    check(err <= TP_TOL, f"tp: overlap vs baseline rel err {err:.3e} > {TP_TOL}")
    log(f"tp: lm.forward batch {batch} x seq {seq} on mesh {dict(mesh.shape)}, overlap vs "
        f"baseline max|diff|/max|baseline| = {err:.3e} (tol {TP_TOL}); "
        f"wall {time.perf_counter() - t0:.1f} s (includes compile)")
    return err


def phase_fused(devices, cfg, *, tokens=2048, seed=0) -> dict:
    """The fused Pallas kernels at the model's TP widths vs the XLA executor."""
    world = len(devices)
    mesh = Mesh(np.asarray(devices), ("model",))
    d, f = cfg.d_model, cfg.d_ff
    cases = {
        # gate/up projection: x row-sharded, w column-sharded
        "ag_matmul": ((tokens, d), (d, 2 * f), (P("model", None), P(None, "model")),
                      P(None, "model")),
        # down projection: x column-sharded, w row-sharded
        "matmul_rs": ((tokens, f), (f, d), (P(None, "model"), P("model", None)),
                      P("model", None)),
    }
    errs, not_mosaic = {}, []
    for i, (kind, (xs, ws, in_specs, out_spec)) in enumerate(cases.items()):
        kx, kw = jax.random.split(jax.random.PRNGKey(seed + i))
        x = jax.device_put(jax.random.normal(kx, xs, jnp.bfloat16), NamedSharding(mesh, in_specs[0]))
        w = jax.device_put((jax.random.normal(kw, ws, jnp.float32) / math.sqrt(ws[0])).astype(
            jnp.bfloat16), NamedSharding(mesh, in_specs[1]))

        def build(be, **kw):
            fn = compile_overlap(kind, BlockChannel(axis="model"), backend=be, **kw)
            return jax.jit(backend.shard_map(fn, mesh, in_specs=in_specs, out_specs=out_spec))

        fused = build("pallas", world_size=world).lower(x, w).compile()
        mosaic = "tpu_custom_call" in fused.as_text()
        if not mosaic:
            not_mosaic.append(kind)
        y, ref = fused(x, w), build("xla")(x, w)
        check(y.shape == ref.shape, f"fused {kind}: shape {y.shape} vs {ref.shape}")
        errs[kind] = _rel_err(y, ref)
        check(errs[kind] <= FUSED_TOL,
              f"fused {kind}: rel err {errs[kind]:.3e} vs the XLA executor > {FUSED_TOL}")
        log(f"fused {kind}: x {xs} w {ws} bf16 over {world} devices, tpu_custom_call "
            f"{'present' if mosaic else 'ABSENT'}, max|diff|/max|xla| = {errs[kind]:.3e} "
            f"(tol {FUSED_TOL})")
    # checked after both kernels ran, so a CPU rehearsal exercises both
    check(not not_mosaic, f"fused {not_mosaic}: no tpu_custom_call in the compiled "
          "program (not Mosaic)")
    return errs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    device = phase_device(args.chips)
    log(f"compile cache: {backend.enable_compile_cache()}")
    cfg = get_config(ARCH)
    if args.chips == 1:
        phase_serve(cfg, backend.make_mesh((1, 1, 1), AXES))
        phase_train(cfg)
    else:
        phase_tp(cfg, backend.make_mesh((1, 1, 4), AXES))
        phase_fused(jax.devices(), cfg)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
