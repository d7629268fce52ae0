"""Read a cell's compared numbers on many seeds, and the control's.

    python3 chipbench/calibrate.py --workload <name> --seeds 11,12,13 --seconds 10 [--control]

Runs the cell once per seed in this one process, as ``run.py`` would, and
prints one JSON line per seed: the checks (each number with the limit it is
held to now) and, with ``--control``, the control's readings of the same
numbers (the reference in bfloat16 in the program's place; for training also
the reference with half of the batch left out).  The limits in the traffic
files are set from these readings; the benchmark's own runs never run the
control.  Needs a TPU, like ``run.py``.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import harness, run
    from repro import backend

    devices = run.tpu_devices()
    import jax

    backend.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        line, ctx = harness.run_cell(args.workload, seed, args.seconds, False, devices, t0,
                                     control=args.control)
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "checks": {k: c["value"] for k, c in line["checks"].items()},
                          "control": ctx.control_readings,
                          "metrics": {k: m["value"] for k, m in line["metrics"].items()},
                          "attempted": line["attempted"], "failed": line["failed"],
                          "wall_s": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
