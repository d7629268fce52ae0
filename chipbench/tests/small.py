"""Cells shrunk to a CPU's size, for the rehearsal tests.

Every width becomes small, the traffic short; the code paths are the chip's.
"""
from __future__ import annotations

import json
import time

import jax

from chipbench import harness

SMALL_MODEL = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
               "vocab_size": 256}
SMALL_MIX = {
    "serve_open_loop": {"rate_per_s": 40.0, "slots": 2, "max_len": 64,
                        "prompt": {"median": 30, "sigma": 0.5, "min": 8, "max": 48},
                        "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                        "check": {"requests": 64, "served_token_gap": 1e-3,
                                  "kv_cache_err": 1e-4}},
    "train": {"batch": 4, "seq": 32,
              "check": {"loss_gap": 1e-4, "grad1_gap": 1e-3, "update3_gap": 1e-3}},
    "prefill": {"seq": 64, "check": {"forwards": 2, "rows": 4, "logit_rel_err": 1e-4}},
}


# The four-chip prefill cell, rehearsed here on virtual devices before a chip
# has run it: the entries a later BENCHMARK.json takes once the cell is measured.
PREFILL = {
    "configs": [{"name": "qwen2-72b", "file": "chipbench/configs/qwen2-72b.json"}],
    "workloads": [{"name": "qwen2-72b.prefill-tp4", "config": "qwen2-72b",
                   "traffic": "prefill-8k", "chips": 4}],
    "end_to_end": [{"name": "prefill_tok_s", "unit": "tokens/s", "better": "higher",
                    "source": "host_clock", "workloads": ["qwen2-72b.prefill-tp4"]}],
    "per_layer": [
        {"name": name, "unit": "%", "better": better, "source": source, "layer": layer,
         "moves": "prefill_tok_s", "workloads": ["qwen2-72b.prefill-tp4"]}
        for name, better, source, layer in (
            ("mfu_prefill", "higher", "host_clock", "model step"),
            ("exposed_comm_share", "lower", "device_trace", "overlap ops"),
            ("idle_share.prefill", "lower", "device_trace", "device"))],
}


def bench() -> dict:
    """BENCHMARK.json with the prefill cell added."""
    b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for key, entries in PREFILL.items():
        have = {e["name"] for e in b[key]}
        b[key] += [e for e in entries if e["name"] not in have]
    return b


def shrink(cell):
    cell.config = dict(cell.config, model=dict(cell.config["model"], **SMALL_MODEL),
                       set=dict(cell.config["set"], **{k: "CPU size" for k in SMALL_MODEL}))
    small = SMALL_MIX[cell.mix["driver"]]
    mix = dict(cell.mix, **{k: v for k, v in small.items() if k != "check"})
    mix["check"] = dict(cell.mix["check"], **small["check"])
    cell.mix = mix


def run(name, seed=7, seconds=1.5, trace=False, control=False):
    """(result line, context) of one small run on the CPU's devices."""
    return harness.run_cell(name, seed, seconds, trace, jax.devices(), time.perf_counter(),
                            bench=bench(), edit=shrink, control=control)
