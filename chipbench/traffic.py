"""One generator for every traffic mix: reads a mix's parameters, returns the
work of one run.

The multiset of sizes and gaps is fixed by the mix and the window: lengths are
the lognormal's quantiles at evenly spaced levels, gaps between arrivals the
exponential's (a Poisson process).  The run's seed draws their order and the
token ids.  So runs with different seeds do the same work in another order.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

__all__ = ["Request", "lognormal_lengths", "arrivals", "open_loop", "rng",
           "token_rows"]


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    at: float  # scheduled arrival, seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    out_len: int


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one use of the seed; any whole number >= 0."""
    return np.random.default_rng([int(seed)] + [int(s) for s in stream])


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int) -> List[int]:
    """n lengths at the lognormal's quantiles (i + 1/2) / n, clipped to [lo, hi]."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def arrivals(n: int, mix: dict) -> List[float]:
    """n gaps between arrivals, in seconds, in a fixed order."""
    kind = mix["arrivals"]
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    rate = float(mix["rate_per_s"])
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """Requests due in a window of ``seconds``: as many as the mean rate puts
    there, with every size and gap drawn as the module says."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    p = mix["prompt"]
    o = mix["output"]
    prompts = lognormal_lengths(n, p["median"], p["sigma"], p["min"], p["max"])
    outs = lognormal_lengths(n, o["median"], o["sigma"], o["min"], o["max"])
    gaps = arrivals(n, mix)
    r = rng(seed, 1)
    prompts = [prompts[i] for i in r.permutation(n)]
    outs = [outs[i] for i in r.permutation(n)]
    gaps = [gaps[i] for i in r.permutation(n)]
    ids = rng(seed, 2)
    reqs, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        if t >= seconds:
            t = seconds * (1.0 - 1e-9)  # a long gap drawn late: still due in the window
        reqs.append(Request(i, t, ids.integers(0, vocab, prompts[i], dtype=np.int32),
                            outs[i]))
    reqs.sort(key=lambda q: q.at)
    return reqs


def token_rows(seed: int, index: int, rows: int, cols: int, vocab: int) -> np.ndarray:
    """Batch ``index`` of ``rows`` x ``cols`` token ids, uniform over the vocabulary."""
    return rng(seed, 3, index).integers(0, vocab, (rows, cols), dtype=np.int32)
