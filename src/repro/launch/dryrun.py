import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# XLA:CPU's AllReducePromotion pass crashes cloning bf16 grad all-reduces
# (CPU-only numerics pass; irrelevant to the TPU target this dry-run models).
os.environ["XLA_FLAGS"] += " --xla_disable_hlo_passes=all-reduce-promotion"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes, print memory/cost analysis, and derive roofline terms.

The two lines above MUST stay first: jax locks the device count on first init.
Never point this module at a TPU: it forces 512 CPU devices at import and
spawns one child process per cell, and a chip belongs to one process.  The
roofline terms it prints model TPU v5e pods (``DRYRUN_KIND``); they are
estimates from the compiler's cost analysis, not measurements.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k            # one cell
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all --out results/dryrun                   # all cells (subprocess each)
"""
import argparse
import json
import subprocess
import sys
import time

__all__ = ["run_cell", "main"]

DRYRUN_KIND = "TPU v5 lite"  # the chip the production meshes are made of


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             mode: str = "overlap", remat: str = "dots", verbose: bool = True,
             extrapolate: bool = True, flow_dtype: str = "float32",
             order: str = "ring", channels: int = 1, attn_bf16: bool = False,
             moe_stream: bool = False):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.launch import specs as S
    from repro.launch import roofline as R
    from repro.parallel.context import ParallelContext
    from repro.training.optimizer import AdamWConfig

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = S.cell_is_applicable(cfg, shape)
    result = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
              "mode": mode}
    if not ok:
        result.update(status="skipped", reason=why)
        if verbose:
            print(json.dumps(result))
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    from repro.core.channels import BlockChannel, CommSpec, CompSpec

    pc = ParallelContext(
        mesh=mesh, mode=mode, dp_axes=dp_axes, attn_p_bf16=attn_bf16,
        moe_decode_stream=moe_stream,
        channel=BlockChannel(axis="model", num_channels=channels,
                             comm=CommSpec(order=order),
                             comp=CompSpec(accum_dtype=flow_dtype)))
    result["variant"] = {"flow_dtype": flow_dtype, "order": order,
                         "channels": channels, "attn_bf16": attn_bf16,
                         "remat": remat, "moe_stream": moe_stream}

    def lower_for(cfg_, unroll):
        """Lower the cell's step function for a config variant."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training.steps import softmax_xent
        from repro.training.optimizer import apply_update

        mod = S.model_module(cfg_)
        params, pspecs = S.abstract_params(cfg_, pc)
        inputs, ispecs = S.input_specs(cfg_, shape, pc)
        def sh(tree):
            return jax.tree_util.tree_map(
                lambda sp_: NamedSharding(mesh, sp_), tree,
                is_leaf=lambda v: isinstance(v, P))

        if shape.kind == "train":
            opt, ospecs = S.abstract_opt_state(params, pspecs)

            def train_step(p, o, batch):
                def loss_fn(pp):
                    logits, aux = mod.forward(
                        pp, cfg_, pc, batch["inputs"],
                        embeds=batch.get("embeds"), remat_policy=remat,
                        unroll=unroll)
                    return softmax_xent(logits, batch["labels"]) + 0.01 * aux

                loss, grads = jax.value_and_grad(loss_fn)(p)
                p2, o2, m = apply_update(p, grads, o, AdamWConfig())
                return p2, o2, {"loss": loss, **m}

            jitted = jax.jit(
                train_step,
                in_shardings=(sh(pspecs), sh(ospecs), sh(ispecs)),
                out_shardings=(sh(pspecs), sh(ospecs), None),
                donate_argnums=(0, 1))
            return jitted.lower(params, opt, inputs)

        if shape.kind == "prefill":
            if cfg_.encoder_layers:
                def prefill_step(p, batch):
                    return mod.forward(p, cfg_, pc, batch["tokens"],
                                       embeds=batch.get("embeds"),
                                       unroll=unroll)
            else:
                def prefill_step(p, batch):
                    return mod.prefill(p, cfg_, pc, batch["tokens"],
                                       embeds=batch.get("embeds"),
                                       max_len=shape.seq_len, unroll=unroll)

            jitted = jax.jit(prefill_step,
                             in_shardings=(sh(pspecs), sh(ispecs)))
            return jitted.lower(params, inputs)

        def serve_step(p, batch):
            return mod.decode_step(p, batch["caches"], cfg_, pc,
                                   batch["tokens"], batch["cache_len"],
                                   unroll=unroll)

        jitted = jax.jit(serve_step,
                         in_shardings=(sh(pspecs), sh(ispecs)),
                         donate_argnums=(1,))
        return jitted.lower(params, inputs)

    def reduced_cfg(u):
        """Config variant with u scan units (prefix/suffix preserved)."""
        import dataclasses as dc
        from repro.models.lm import layer_plan
        if cfg.encoder_layers:
            return dc.replace(cfg, encoder_layers=u, n_layers=u)
        _, unit, _, suffix = layer_plan(cfg)
        k0 = cfg.moe.first_k_dense if cfg.moe else 0
        return dc.replace(cfg, n_layers=k0 + u * len(unit) + len(suffix))

    def analyze(compiled):
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
        cb, ck = R.parse_collective_bytes(compiled.as_text())
        return {"flops": float(cost.get("flops", 0) or 0),
                "bytes": float(cost.get("bytes accessed", 0) or 0),
                "coll": cb, "kinds": ck}

    # 1) full-depth scanned compile -> memory analysis (true buffer liveness)
    t0 = time.time()
    lowered = lower_for(cfg, unroll=False)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()

    # 2) two unrolled reduced-depth compiles -> per-unit cost extrapolation
    #    (XLA cost analysis counts while bodies once, so scanned costs are
    #     depth-independent; unrolled variants expose the real per-unit cost)
    from repro.models.lm import layer_plan
    if cfg.encoder_layers:
        n_units = cfg.n_layers
    else:
        _, _, n_units, _ = layer_plan(cfg)
    if not extrapolate:
        # multi-pod pass is compile-success + memory proof; roofline terms are
        # reported from the single-pod table (assignment §ROOFLINE)
        result.update(
            status="ok", n_chips=512 if multi_pod else 256,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={k: getattr(mem, k, None) for k in
                    ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes")} if mem is not None else None,
            extrapolated=False,
        )
        if verbose:
            print(json.dumps(result, default=str))
        return result

    u1, u2 = 1, 2
    c1 = analyze(lower_for(reduced_cfg(u1), unroll=True).compile())
    c2 = analyze(lower_for(reduced_cfg(u2), unroll=True).compile())

    def extrap(k):
        per_unit = c2[k] - c1[k]
        return c1[k] + (n_units - u1) * per_unit

    flops = extrap("flops")
    byts = extrap("bytes")
    coll = extrap("coll")
    kinds = {k: c1["kinds"].get(k, 0.0)
             + (n_units - u1) * (c2["kinds"].get(k, 0.0) - c1["kinds"].get(k, 0.0))
             for k in set(c1["kinds"]) | set(c2["kinds"])}

    terms = R.roofline_terms({"flops": flops, "bytes accessed": byts}, coll, kind=DRYRUN_KIND)
    n_chips = 512 if multi_pod else 256
    mf = R.model_flops(cfg, shape)
    useful = mf / max(flops * n_chips, 1.0)

    result.update(
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory={k: getattr(mem, k, None) for k in
                ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes")} if mem is not None else None,
        cost={"flops": flops, "bytes_accessed": byts,
              "per_unit_flops": c2["flops"] - c1["flops"], "n_units": n_units},
        collective_bytes=coll,
        collective_kinds=kinds,
        roofline={k: terms[k] for k in ("compute_s", "memory_s", "collective_s")},
        dominant=R.dominant(terms),
        model_flops=mf,
        useful_flops_ratio=round(useful, 4),
    )
    if verbose:
        print(json.dumps(result, default=str))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="overlap",
                    choices=["overlap", "baseline"])
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--flow-dtype", default="float32")
    ap.add_argument("--order", default="ring")
    ap.add_argument("--channels", type=int, default=1)
    ap.add_argument("--attn-bf16", action="store_true")
    ap.add_argument("--moe-stream", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    if not args.all:
        res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                       mode=args.mode, remat=args.remat,
                       extrapolate=not args.multi_pod,
                       flow_dtype=args.flow_dtype, order=args.order,
                       channels=args.channels, attn_bf16=args.attn_bf16,
                       moe_stream=args.moe_stream)
        sys.exit(0 if res["status"] in ("ok", "skipped") else 1)

    # --all: one subprocess per cell (isolates compile memory; parallelizable)
    import itertools
    from repro.configs import ARCH_NAMES
    from repro.configs.base import SHAPES

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape, mp in itertools.product(
            ARCH_NAMES, SHAPES, (False, True)):
        tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}__{args.mode}"
        out_file = os.path.join(args.out, tag + ".json")
        if os.path.exists(out_file):
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--mode", args.mode, "--remat", args.remat]
        if mp:
            cmd.append("--multi-pod")
        t0 = time.time()
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=3600)
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        try:
            res = json.loads(line)
        except json.JSONDecodeError:
            res = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "status": "error", "stderr": proc.stderr[-2000:]}
        with open(out_file, "w") as f:
            json.dump(res, f, indent=1)
        print(f"{tag}: {res['status']} ({time.time()-t0:.0f}s)")
        if res["status"] == "error":
            failures.append(tag)
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("all cells ok")


if __name__ == "__main__":
    main()
