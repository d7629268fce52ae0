"""Roofline-term derivation from compiled dry-run artifacts, per chip kind.

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / link_bw

``compiled.cost_analysis()`` on the post-SPMD module reports *per-device*
flops/bytes (the module IS the per-device program); the assignment's
"HLO_FLOPs / (chips × peak)" is therefore applied with HLO_FLOPs per device.

collective_bytes is parsed from the optimized HLO text: for each collective op
we take its output payload and weight it by the ring traffic factor for its
replica-group size g (all-gather & reduce-scatter move (g-1)/g of the payload
per link hop; all-reduce = RS+AG = 2(g-1)/g; collective-permute & all-to-all
move the payload once).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Tuple

from repro.backend import hw as _hw

__all__ = ["HW", "parse_collective_bytes", "roofline_terms", "model_flops"]

# Published peaks per chip keyed by jax ``device_kind`` (bf16 FLOP/s, HBM
# bytes/s, bytes/s per ICI link), with their source: the one hardware table.
HW = _hw.CHIPS


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?((?:[a-z0-9]+)\[[0-9,]*\][^)]*?)(?:\))?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{[0-9]+,[0-9]+\},?)+)\}")
_PAIR_RE = re.compile(r"\{([0-9]+),([0-9]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collective_bytes(hlo_text: str) -> Tuple[float, Dict[str, float]]:
    """Per-device collective bytes (ring-weighted) from post-SPMD HLO text.

    Returns (total_bytes, per-kind breakdown).  ``-start`` counted, ``-done``
    skipped.  collective-permutes are accounted **per link direction**: ICI
    links are full-duplex, so a bidirectional ring that splits its payload
    across the +1 and -1 directions loads each link with half the bytes — the
    busiest direction is what gates time.  Direction is classified from
    ``source_target_pairs`` (dst-src sign for the majority of pairs).
    """
    per_kind: Dict[str, float] = defaultdict(float)
    permute_dirs: Dict[int, float] = defaultdict(float)
    for line in hlo_text.splitlines():
        line = line.strip()
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        payload = _shape_bytes(shape_str)
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len([x for x in gm.group(1).split(",") if x.strip()])
        if kind == "all-gather":
            w = (g - 1) / g if g > 1 else 0.0
        elif kind == "reduce-scatter":
            w = (g - 1) if g > 1 else 0.0  # payload is post-scatter (1/g size)
        elif kind == "all-reduce":
            w = 2 * (g - 1) / g if g > 1 else 0.0
        elif kind == "collective-permute":
            pm = _PAIRS_RE.search(line)
            direction = 1
            if pm:
                votes = 0
                pairs = _PAIR_RE.findall(pm.group(1))
                for a, b in pairs[: min(8, len(pairs))]:
                    votes += 1 if int(b) > int(a) else -1
                direction = 1 if votes >= 0 else -1
            permute_dirs[direction] += payload
            per_kind[kind] += payload
            continue
        else:  # all-to-all
            w = (g - 1) / g if g > 1 else 0.0
        per_kind[kind] += payload * w
    # busiest permute direction gates time; other kinds assumed same-direction
    permute_link = max(permute_dirs.values()) if permute_dirs else 0.0
    non_permute = sum(v for k, v in per_kind.items()
                      if k != "collective-permute")
    return non_permute + permute_link, dict(per_kind)


def roofline_terms(cost: dict, collective_bytes: float, kind: str) -> Dict[str, float]:
    """Three roofline terms (seconds) on a ``kind`` chip from per-device cost
    analysis; a kind not in :data:`HW` raises."""
    hw = _hw.chip(kind)
    flops = float(cost.get("flops", 0.0) or 0.0)
    byts = float(cost.get("bytes accessed", 0.0) or 0.0)
    return {
        "compute_s": flops / hw.peak_flops,
        "memory_s": byts / hw.hbm_bw,
        "collective_s": collective_bytes / hw.link_bw,
        "flops": flops,
        "bytes": byts,
        "collective_bytes": collective_bytes,
    }


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); D = tokens processed.

    train counts fwd+bwd (6ND); prefill counts 2ND; decode counts 2ND per
    generated token (D = batch tokens for the one step)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n_active * tokens


def dominant(terms: Dict[str, float]) -> str:
    keys = ("compute_s", "memory_s", "collective_s")
    return max(keys, key=lambda k: terms[k])
