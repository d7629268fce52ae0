"""Production training driver.

Wires together: config -> mesh (elastic) -> model init/shard -> data pipeline
-> jit'd train step (TileLink overlap on by default) -> async checkpointing ->
watchdog/straggler monitoring -> resilient restart loop.

Example (CPU dev run):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \\
      --steps 50 --batch 8 --seq 256 --reduce --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import backend
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import SyntheticLM
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import model_module
from repro.parallel.context import ParallelContext
from repro.parallel.sharding import place
from repro.runtime import StepWatchdog, ElasticMesh
from repro.training import AdamWConfig, init_opt_state, make_train_step

__all__ = ["train", "reduce_config", "main"]


def reduce_config(cfg, d_model=128, vocab=512):
    """Reduced same-family config for CPU dev/smoke runs."""
    kw = dict(
        n_layers=len(cfg.pattern) * 2 + (cfg.moe.first_k_dense if cfg.moe else 0),
        d_model=d_model, vocab_size=vocab)
    if cfg.n_heads:
        kw.update(n_heads=8, n_kv_heads=min(cfg.n_kv_heads, 4), head_dim=16)
    if cfg.d_ff:
        kw.update(d_ff=d_model * 2)
    if cfg.moe:
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=8, top_k=min(2, cfg.moe.top_k), d_expert=64,
            dense_d_ff=d_model * 2)
    if cfg.ssm:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, headdim=16, chunk=16)
    if cfg.encoder_layers:
        kw.update(encoder_layers=2, enc_len=32)
    return dataclasses.replace(cfg, **kw)


def train(arch, *, steps=100, batch=8, seq=256, reduce=True,
          mode="overlap", ckpt_dir=None, ckpt_every=50, lr=3e-4,
          production_mesh=False, dtype=jnp.float32, log_every=10,
          resume=True, on_step=None, remat_policy="dots"):
    """Train ``arch`` (a registered name or an ArchConfig); returns the losses.

    ``on_step(step, metrics)``, when given, sees each step's metrics as
    Python floats (loss, lr, grad_norm, ...).  ``remat_policy`` is
    ``lm.forward``'s: "dots" saves the matmul outputs, "full" recomputes
    each layer from its input (full-width smollm-360m in f32 at 8 x 1024
    tokens needs it to fit one 16 GB v5e).
    """
    cfg = get_config(arch) if isinstance(arch, str) else arch
    arch = cfg.name
    if reduce:
        cfg = reduce_config(cfg)
    mod = model_module(cfg)

    elastic = ElasticMesh(target_model=16 if production_mesh else 2)
    mesh, usable = (make_production_mesh(), 256) if production_mesh else elastic.build()
    pc = ParallelContext(mesh=mesh, mode=mode)

    params = mod.init(jax.random.PRNGKey(0), cfg, pc, dtype)
    pspecs = mod.specs(cfg, pc)
    params = place(params, mesh, pspecs)
    opt_state = init_opt_state(params)
    opt_state = place(opt_state, mesh,
                      {"mu": pspecs, "nu": pspecs, "step": jax.sharding.PartitionSpec()})

    opt_cfg = AdamWConfig(lr=lr, total_steps=steps, warmup_steps=max(5, steps // 20))
    masks = mod.grad_masks(cfg, pc)
    step_fn = make_train_step(mod, cfg, pc, opt_cfg, remat_policy=remat_policy,
                              grad_masks=masks)

    pipe = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if mgr and resume and mgr.latest_step() is not None:
        s0 = mgr.latest_step()
        (restored, meta) = mgr.restore(
            s0, {"params": params, "opt": opt_state}, mesh,
            {"params": pspecs,
             "opt": {"mu": pspecs, "nu": pspecs,
                     "step": jax.sharding.PartitionSpec()}})
        params, opt_state = restored["params"], restored["opt"]
        pipe.restore(meta["extra"]["data"])
        start = s0
        print(f"resumed from step {s0}")

    wd = StepWatchdog()
    losses = []
    for step in range(start, steps):
        batch_np = pipe.host_batch()
        wd.start()
        params, opt_state, metrics = step_fn(params, opt_state, batch_np)
        straggler = wd.stop()
        losses.append(float(metrics["loss"]))
        if on_step is not None:
            on_step(step, {k: float(v) for k, v in metrics.items()})
        if step % log_every == 0 or step == steps - 1:
            print(f"step {step}: loss={losses[-1]:.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"med_step={wd.median()*1e3:.0f}ms"
                  + (" [STRAGGLER]" if straggler else ""))
        if mgr and ckpt_every and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, params, opt_state,
                     extra={"data": pipe.state(), "arch": arch})
    if mgr:
        mgr.save(steps, params, opt_state,
                 extra={"data": pipe.state(), "arch": arch})
        mgr.wait()
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mode", default="overlap", choices=["overlap", "baseline"])
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--full", dest="reduce", action="store_false")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    backend.enable_compile_cache()
    losses = train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                   reduce=args.reduce, mode=args.mode, ckpt_dir=args.ckpt_dir,
                   ckpt_every=args.ckpt_every, lr=args.lr,
                   production_mesh=args.production_mesh)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")


if __name__ == "__main__":
    main()
