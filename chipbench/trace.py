"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load` turns the ``.xplane.pb`` the JAX profiler writes into plain
lists: each chip's device operations and the host spans the benchmark
opened (names starting ``chipbench.``).  :func:`reduce` works on those lists
alone, so it is tested on constructed traces.  All times are nanoseconds on
the profiler's one clock; the window is the ``chipbench.window`` span.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

__all__ = ["load", "reduce", "union", "covered", "is_collective", "op_name",
           "op_label", "WINDOW_SPAN"]

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start, end

WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"\bsend\b|\brecv\b|send-done|recv-done", re.IGNORECASE)


def op_name(text: str) -> str:
    """The instruction's name from a trace event's HLO text:
    "%fusion.4 = f32[8]{0} fusion(...)" -> "fusion.4"."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def op_label(text: str) -> str:
    """The name and the result's shape, for the breakdown."""
    name, _, rest = text.partition(" = ")
    shape = rest.split(" ", 1)[0] if rest else ""
    return f"{name.lstrip('%')} {shape}".strip()[:120]


def is_collective(text: str) -> bool:
    return bool(_COLLECTIVE.search(op_name(text)))


def load(path: str) -> dict:
    """{"devices": {chip: [Event]}, "host": [Event]} from one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    seen = []
    for plane in pd.planes:
        seen.append(plane.name)
        m = _DEVICE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                raise ValueError(f"device plane {plane.name} has no {OPS_LINE!r} line: "
                                 f"{sorted(lines)}")
            devices[int(m.group(1))] = [(e.name, e.start_ns, e.end_ns)
                                        for e in lines[OPS_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns) for e in line.events
                            if e.name.startswith("chipbench."))
    if not devices:
        raise ValueError(f"no TPU device plane in the trace; planes: {seen}")
    return {"devices": devices, "host": host}


def union(intervals) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events if b > lo and a < hi]


def _subtract(base: List[Interval], cut: List[Interval]) -> List[Interval]:
    """Parts of the (merged) ``base`` that no interval of (merged) ``cut`` covers."""
    out, j = [], 0
    for a, b in base:
        cur = a
        while j < len(cut) and cut[j][1] <= cur:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _self_times(events):
    """(name, self seconds) of each event of one line, where an event that
    encloses others (a loop, a call) keeps only the time its children leave."""
    out, stack = [], []  # stack of [name, start, end, child time]
    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += min(b, stack[-1][2]) - a
        stack.append([n, a, b, 0.0])
    out.extend((t[0], t[2] - t[1] - t[3]) for t in stack)
    return out


def _label(gap: Interval, spans: List[Event]) -> str:
    """The innermost host span open at the gap's middle."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for n, a, b in spans:
        if a <= mid < b and (best is None or b - a < best[2] - best[1]):
            best = (n, a, b)
    return best[0][len("chipbench."):] if best else "no span"


def reduce(tr: dict, top: int = 10) -> dict:
    """Numbers of the traced window, from :func:`load`'s lists.

    Returns window_s, and per chip busy_s and exposed_comm_s (time in which a
    collective runs and no other operation does), plus the ``breakdown`` the
    result line carries: the operations that took most device time (seconds
    averaged over the chips) and the longest idle gaps of any chip, each
    labelled with the host span open during it.
    """
    wins = [(a, b) for n, a, b in tr["host"] if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span in the trace, found {len(wins)}")
    lo, hi = wins[0]
    spans = [e for e in tr["host"] if e[0] != WINDOW_SPAN]
    chips, op_time, gaps = {}, defaultdict(float), []
    for chip, events in sorted(tr["devices"].items()):
        ev = _clip(events, lo, hi)
        busy = union((a, b) for _, a, b in ev)
        comm = union((a, b) for n, a, b in ev if is_collective(n))
        other = union((a, b) for n, a, b in ev if not is_collective(n))
        chips[chip] = {"busy_s": covered(busy) * 1e-9,
                       "exposed_comm_s": covered(_subtract(comm, other)) * 1e-9}
        for n, t in _self_times(ev):
            op_time[op_label(n)] += t * 1e-9
        idle = _subtract([(lo, hi)], busy)
        gaps.extend(idle)
    n_chips = max(1, len(chips))
    ops = sorted(((n, t / n_chips) for n, t in op_time.items()), key=lambda x: -x[1])[:top]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "chips": chips,
        "breakdown": {
            "device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[_label(g, spans), (g[1] - g[0]) * 1e-9] for g in longest],
        },
    }
