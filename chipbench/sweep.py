"""Find the knee of an open-loop serving cell: the highest rate at which the
time to first token does not grow over the window and the backlog stays
bounded.

    python3 chipbench/sweep.py --workload smollm-360m.chat --seeds 5,6 --seconds 200 \
        --drain 150 --rates 0.2,0.3

Builds the engine once, then sends the cell's traffic at each rate, once for
each seed (the seed orders the mix's sizes and gaps, as in a run; the engine
is drained between windows), and prints one JSON line per window and one per
rate with the seeds' windows pooled: requests, unfinished ones (not done
``--drain`` seconds after the window), the most requests left waiting for a
slot after a step, p50 and p90 time to first token, p90 time to first token
per prefill chunk of the requests due in the first and in the last third of
the window (a prompt's length sets its time to first token far more than the
load does, so the thirds are compared per chunk), requests in flight when
the last one arrives, time to drain after it, median step time and p90 time
per output token.  A window far longer than a request's latency shows growth
that a run's window is too short to show.

A rate is sustained when, pooled over its seeds, every request finished, no
request ever waited for a slot, and the last third's p90 per chunk is at most
twice the first third's (the first third holds the ramp from an empty engine,
which alone reads up to 1.7 times where no request waits for a slot).  The knee, printed last, is the highest rate
that is sustained with every lower rate swept.  The cell's rate is then fixed
in its traffic file at about four fifths of the knee.  Needs a TPU.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def summary(ws, seconds, chunk) -> dict:
    """Readings of the windows ``ws`` (one or more), their requests pooled.
    A window carries ``chunks``, each request's count of prefill chunks of
    ``chunk`` tokens, beside what the serving driver's window returns."""
    from chipbench.readings import percentile

    def ms(v):
        return None if v is None else 1e3 * v

    ttft, per_chunk, early, late, tpot, step = [], [], [], [], [], []
    in_flight, drain, unfinished, slot_queue = [], [], 0, 0
    for w in ws:
        at, first, last, n = w["at"], w["first"], w["last"], w["tokens"]
        ttft.append(first - at)
        per_chunk.append((first - at) / np.asarray(w["chunks"]))
        early.append(at < seconds / 3)
        late.append(at >= 2 * seconds / 3)
        slot_queue = max(slot_queue, w["slot_queue"])
        tpot.append(np.where(n > 1, (last - first) / np.maximum(n - 1, 1), np.nan))
        step += [dt for dt, _ in w.get("work", [])]
        done_by = np.where(np.isnan(last), np.inf, last)
        in_flight.append(int(np.sum(done_by > at.max())))
        drain.append(float(np.nanmax(last) - at.max()))
        unfinished += int(np.sum(np.isnan(last)))
    ttft, per_chunk, early, late, tpot = (np.concatenate(a) for a in
                                          (ttft, per_chunk, early, late, tpot))
    out = {"requests": int(len(ttft)), "unfinished": unfinished,
           "slot_queue_max": slot_queue,
           "ttft_p50_ms": ms(percentile(ttft, 50)), "ttft_p90_ms": ms(percentile(ttft, 90)),
           "per_chunk_p90_first_third_ms": ms(percentile(per_chunk[early], 90)),
           "per_chunk_p90_last_third_ms": ms(percentile(per_chunk[late], 90)),
           "in_flight_at_last_arrival": in_flight, "drain_s": drain,
           "step_p50_ms": ms(float(np.median(step))) if step else None,
           "tpot_p90_ms": ms(percentile(tpot, 90))}
    a, b = out["per_chunk_p90_first_third_ms"], out["per_chunk_p90_last_third_ms"]
    out["sustained"] = bool(unfinished == 0 and slot_queue == 0 and a and b and b <= 2 * a)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--drain", type=float, default=60.0,
                    help="seconds past the window to wait for its requests")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from chipbench import harness, run, traffic
    from chipbench.drivers import serve_open_loop as serve
    from repro import backend

    devices = run.tpu_devices()
    import jax

    backend.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    ctx = harness.Context(cell, seeds[0], args.seconds, False, devices[: cell.chips],
                          time.perf_counter())
    engine = serve.build(ctx)
    chunk = engine.prefill_chunk
    knee, below = None, True
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        ws = []
        for seed in seeds:
            reqs = traffic.open_loop(mix, seed, args.seconds, ctx.arch.vocab_size)
            w = serve.window(ctx, engine, reqs, args.seconds, args.drain)
            engine.drain()  # what the drain limit cut off, before the next window
            w["chunks"] = [-(-len(r.prompt) // chunk) for r in reqs]
            ws.append(w)
            print(json.dumps(dict(rate_per_s=rate, seed=seed,
                                  **summary([w], args.seconds, chunk))), flush=True)
        pooled = summary(ws, args.seconds, chunk)
        print(json.dumps(dict(rate_per_s=rate, seed="pooled", **pooled)), flush=True)
        below = below and pooled["sustained"]
        if below:
            knee = rate
    print(json.dumps({"knee_per_s": knee}), flush=True)


if __name__ == "__main__":
    main()
