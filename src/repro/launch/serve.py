"""Serving driver: load (or init) a model and serve continuous-batching
requests through the request-level engine.

Example (CPU dev run):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduce \\
      --prompt-len 16 --new-tokens 16 --batch 4 --slots 2 --temperature 0.7
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import backend
from repro.configs import get_config
from repro.launch.mesh import make_dev_mesh
from repro.launch.train import reduce_config
from repro.models import lm
from repro.parallel.context import ParallelContext
from repro.parallel.sharding import place
from repro.serving import Request, ServeEngine
from repro.checkpoint import CheckpointManager

__all__ = ["serve", "main"]


def serve(cfg, mesh, *, n_requests=4, prompt_len=16, new_tokens=16, slots=8,
          decode_block=32, mode="overlap", temperature=0.0, top_k=0,
          eos_id=None, seed=0, ckpt_dir=None):
    """Serve ``n_requests`` random prompts (made from ``seed``) with random
    weights (or the latest checkpoint in ``ckpt_dir``) on ``mesh``.

    Returns {"tokens": one array per request, "stats": the engine's
    counters, "setup_s": init + placement + engine build, "drain_s": the
    requests' wall time, which includes compiling the step}.
    """
    if cfg.encoder_layers:
        raise SystemExit("serve.py drives decoder-only archs; enc-dec decode "
                         "is exercised in tests/test_models.py")
    t0 = time.perf_counter()
    pc = ParallelContext(mesh=mesh, mode=mode)
    params = place(lm.init(jax.random.PRNGKey(seed), cfg, pc, jnp.float32),
                   mesh, lm.specs(cfg, pc))
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir)
        s0 = mgr.latest_step()
        if s0 is not None:
            (restored, _) = mgr.restore(s0, {"params": params, "opt": None})
            params = place(restored["params"], mesh, lm.specs(cfg, pc))
            print(f"loaded checkpoint step {s0}")

    engine = ServeEngine(cfg, pc, params, max_len=prompt_len + new_tokens,
                         temperature=temperature, n_slots=slots,
                         decode_block=decode_block)
    jax.block_until_ready(engine.pool.caches)
    t1 = time.perf_counter()
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(n_requests, prompt_len), dtype=np.int32)
    handles = [
        engine.submit(Request(tokens=row, max_new_tokens=new_tokens,
                              temperature=temperature, top_k=top_k,
                              eos_id=eos_id, seed=seed + i))
        for i, row in enumerate(prompts)
    ]
    outs = engine.drain(handles)
    t2 = time.perf_counter()
    return {"tokens": [outs[h] for h in handles], "stats": dict(engine.stats),
            "setup_s": t1 - t0, "drain_s": t2 - t1}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--mode", default="overlap", choices=["overlap", "baseline"])
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = full vocab)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a request early when this token is sampled")
    ap.add_argument("--slots", type=int, default=8,
                    help="batch slots in the KV-cache pool; requests beyond "
                         "this queue and admit as slots free up")
    ap.add_argument("--decode-block", type=int, default=32,
                    help="max tokens decoded on device per step (one host "
                         "sync per step regardless)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    backend.enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg)
    res = serve(cfg, make_dev_mesh(), n_requests=args.batch,
                prompt_len=args.prompt_len, new_tokens=args.new_tokens,
                slots=args.slots, decode_block=args.decode_block,
                mode=args.mode, temperature=args.temperature, top_k=args.top_k,
                eos_id=args.eos_id, seed=args.seed, ckpt_dir=args.ckpt_dir)
    n_tok = sum(len(t) for t in res["tokens"])
    st, dt = res["stats"], res["drain_s"]
    print(f"generated {n_tok} tokens over {args.batch} requests in {dt:.2f}s "
          f"wall, compile included ({st['steps']} steps, "
          f"{st['host_syncs']} host syncs, {st['step_traces']} trace)")
    print("sample:", res["tokens"][0].tolist())


if __name__ == "__main__":
    main()
