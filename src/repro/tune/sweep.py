"""Successive-halving measured sweep with analytic screening + early exit.

The joint ``CommSpec x CompSpec`` space is ~hundreds of points per shape
(ROADMAP) — far too many to time naively at full repeats.  This module is
the measured ranker's search strategy, structured as three shrinking rounds
in the successive-halving spirit (cf. the Flux / Triton-distributed
autotuners in PAPERS.md):

  1. **rank** — the whole space is ordered by the analytic cost model
     (``tune/cost.py``): free, trace-safe host arithmetic;
  2. **screen** — only a cost-ordered *prefix* (``screen_fraction`` of the
     space, at least ``min_screen`` points) is timed at all, with a cheap
     1-repeat screen through the shared :class:`~repro.tune.measure.CaseTimer`
     (operands built once, compile time AOT-split out); everything past the
     prefix is pruned unmeasured;
  3. **promote** — the best ``keep_fraction`` of the screen, re-ordered by
     screen time, gets full-repeat ``(median, iqr)`` timing.  The loop stops
     early once the incumbent beats the next candidate's screen time by more
     than its own noise band — ``screen > median + iqr`` — so measurement
     noise WIDENS the search instead of shrinking it: a screen below the
     incumbent's plausible range still gets timed, and screens are sorted
     ascending, so past the cut no remaining candidate can plausibly win.

``measured_sweep`` takes the timer as a callable so tests and the CI smoke
can substitute a deterministic oracle — on the emulated CPU target wall time
is not a perf signal (ROADMAP), but the pruning *algorithm* (prefix size,
early exit, winner agreement with the exhaustive sweep) is deterministic and
is asserted in ``tests/test_tune.py``.

Environment knobs (also surfaced in README.md):

  ``REPRO_TUNE_SWEEP``         "0" disables pruning — every candidate is
                               timed at full repeats (the exhaustive sweep);
  ``REPRO_TUNE_SWEEP_SCREEN``  fraction of the cost-ordered space screened
                               (default 0.4);
  ``REPRO_TUNE_SWEEP_KEEP``    fraction of the screen promoted to
                               full-repeat timing (default 0.25).
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from repro.tune import cost as _cost
from repro.tune.candidates import Candidate

__all__ = ["SweepConfig", "SweepResult", "sweep_config_from_env", "measured_sweep"]

_ENV_ENABLE = "REPRO_TUNE_SWEEP"
_ENV_SCREEN = "REPRO_TUNE_SWEEP_SCREEN"
_ENV_KEEP = "REPRO_TUNE_SWEEP_KEEP"

# a Timer maps (candidate, repeats=, warmup=) -> (median_us, iqr_us)
Timer = Callable[..., Tuple[float, float]]


@dataclasses.dataclass(frozen=True)
class SweepConfig:
    """Knobs of the pruned sweep (env-derived via :func:`sweep_config_from_env`)."""

    enabled: bool = True
    screen_fraction: float = 0.4  # cost-ordered prefix that is timed at all
    keep_fraction: float = 0.25  # screened fraction promoted to full repeats
    min_screen: int = 4  # small spaces: never screen fewer than this
    min_keep: int = 2

    def __post_init__(self):
        if not (0.0 < self.screen_fraction <= 1.0 and 0.0 < self.keep_fraction <= 1.0):
            raise ValueError(
                f"sweep fractions must be in (0, 1]: screen={self.screen_fraction}, "
                f"keep={self.keep_fraction}"
            )


def sweep_config_from_env() -> SweepConfig:
    """Config with the ``REPRO_TUNE_SWEEP*`` environment overrides applied."""
    kw: Dict[str, Any] = {}
    flag = os.environ.get(_ENV_ENABLE)
    if flag is not None:
        kw["enabled"] = flag.strip().lower() not in ("0", "false", "off", "no")
    screen = os.environ.get(_ENV_SCREEN)
    if screen:
        kw["screen_fraction"] = float(screen)
    keep = os.environ.get(_ENV_KEEP)
    if keep:
        kw["keep_fraction"] = float(keep)
    return SweepConfig(**kw)


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Winner of one measured sweep plus the pruning ledger."""

    winner: Candidate
    median_us: float
    iqr_us: float
    stats: Dict[str, Any]  # total/screened/timed/pruned/early_exit (cache v3)


def _exhaustive(cands, timer, repeats, warmup) -> SweepResult:
    best, best_med, best_iqr = None, float("inf"), 0.0
    for cand in cands:
        med, iqr = timer(cand, repeats=repeats, warmup=warmup)
        if med < best_med:  # strict: ties keep enumeration order
            best, best_med, best_iqr = cand, med, iqr
    stats = {
        "total": len(cands),
        "screened": len(cands),
        "timed": len(cands),
        "pruned": 0,
        "early_exit": False,
    }
    return SweepResult(winner=best, median_us=best_med, iqr_us=best_iqr, stats=stats)


def measured_sweep(
    kind: str,
    sig: Sequence[int],
    world: int,
    cands: Sequence[Candidate],
    timer: Timer,
    *,
    repeats: int = 3,
    warmup: int = 1,
    config: Optional[SweepConfig] = None,
) -> SweepResult:
    """Pruned measured search over ``cands`` (module docstring for the shape).

    ``timer(cand, repeats=, warmup=)`` must return ``(median_us, iqr_us)``;
    ``repeats``/``warmup`` here apply to the full-timing round (the screen
    always uses one repeat).  Disabled or degenerate configs fall back to
    the exhaustive full-repeat sweep so the winner contract never weakens.
    """
    if not cands:
        raise ValueError("measured_sweep needs at least one candidate")
    cfg = config or sweep_config_from_env()
    n = len(cands)
    n_screen = min(n, max(cfg.min_screen, math.ceil(cfg.screen_fraction * n)))
    if not cfg.enabled or n_screen >= n:
        return _exhaustive(cands, timer, repeats, warmup)

    sig = tuple(int(s) for s in sig)
    order = sorted(range(n), key=lambda i: _cost.predict_cost(kind, sig, world, cands[i]))
    screened = []
    for i in order[:n_screen]:
        med, _ = timer(cands[i], repeats=1, warmup=warmup)
        screened.append((i, med))
    # stable sort: model-order ties resolve toward the cheaper predicted point
    screened.sort(key=lambda t: t[1])
    n_keep = min(len(screened), max(cfg.min_keep, math.ceil(cfg.keep_fraction * len(screened))))

    best, best_med, best_iqr, timed, early = None, float("inf"), 0.0, 0, False
    for i, screen_us in screened[:n_keep]:
        if best is not None and screen_us > best_med + best_iqr:
            # the incumbent beats every remaining screen (ascending) by more
            # than its own noise band: nothing left can plausibly win
            early = True
            break
        med, iqr = timer(cands[i], repeats=repeats, warmup=warmup)
        timed += 1
        if med < best_med:
            best, best_med, best_iqr = cands[i], med, iqr
    assert best is not None  # n_keep >= 1 and the first iteration always times
    stats = {
        "total": n,
        "screened": n_screen,
        "timed": timed,
        "pruned": n - n_screen,
        "early_exit": early,
    }
    return SweepResult(winner=best, median_us=best_med, iqr_us=best_iqr, stats=stats)
