"""Run one cell of the benchmark on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's ``workloads``.  Set-up (weights from
the seed, compilation, warm-up) is ``setup_s``; then the cell's driver runs
its traffic for ``--seconds``, and checks what that window produced against
the plain reference.  ``--trace 1`` runs the same window with the profiler on
over a few seconds of it and reports the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced runs)
and ``checks``, each number compared beside its limit.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result: there is no CPU fallback.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def tpu_devices():
    """The TPUs JAX sees, or exit non-zero."""
    import jax

    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX platform "
                         f"{devices[0].platform if devices else None!r})")
    return devices


def main(argv=None):
    args = parse(argv)
    from chipbench import harness
    from repro import backend

    devices = tpu_devices()
    if backend.target() != "tpu":
        raise SystemExit(f"chipbench: backend target is {backend.target()!r}; unset "
                         "REPRO_BACKEND")
    import jax

    backend.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    line, _ = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                            devices, T0)
    harness.print_result(line)


if __name__ == "__main__":
    main()
