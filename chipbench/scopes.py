"""Device time by program scope, and the program's own host spans.

The program names its device work with ``jax.named_scope`` (``layers``,
``attn``, ``kv_write``, ``decode_pass``, ``overlap.ag_matmul``, ...) and its
host work with ``jax.profiler.TraceAnnotation`` spans named ``repro.*``.
:func:`load` reads what :func:`chipbench.trace.load` reads and besides keeps
each device operation's scope path and the ``repro.*`` spans with their
arguments; :func:`reduce` returns what :func:`chipbench.trace.reduce` returns
and adds:

* ``scopes``: {chip: {scope path: self seconds}} over the traced slice;
* ``scope_ops``: {chip: {scope path: {operation: [events, self seconds]}}};
* ``spans``: every ``repro.*`` span, [name, start, end, arguments], in
  seconds from the slice's start;
* ``steps``: for each ``repro.serve.step`` span that lies wholly in the
  slice, the decode passes its ``repro.serve.post`` span reports and the
  passes the chips ran in it (events of the decode pass's head matmul);
* ``breakdown.device_scopes``: the scope paths with the most self time,
  averaged over the chips; and ``breakdown.idle_gaps`` with each gap
  labelled by the innermost open span of either family.

:func:`readings` turns that into the per-layer numbers ``decode_pass_ms``,
``kv_cache_share.serve``, ``serve_host_ms`` and ``attn_share.train``.  The
harness does not call this module yet; run a cell through it with

    python3 chipbench/scopes.py --workload <name> --seed <n> --seconds <s>

which prints the result line of a traced run and then one line of readings.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import trace  # noqa: E402

__all__ = ["load", "reduce", "readings", "scope_path", "decode_pass_ms", "kv_cache_share",
           "serve_host_ms", "attn_share", "unscoped_share", "NO_SCOPE", "SPAN_PREFIX"]

SPAN_PREFIX = "repro."
NO_SCOPE = "(unscoped)"
# name-stack entries JAX adds itself: control flow, calls and rematerialisation
_STRUCTURAL = {"while", "body", "cond", "closed_call", "checkpoint", "remat",
               "rematted_computation", "shard_map", "pjit", "scan",
               "custom_jvp_call", "custom_vjp_call"}
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# the engine's host work beside waiting for the device (not its fetch)
HOST_WORK = ("admit", "prep", "dispatch", "post")


def _scope(entry: str) -> Optional[str]:
    """One entry of an operation's name stack as a program scope, or None
    for what JAX adds itself: transforms are unwrapped (``transpose(jvp(attn))``
    is ``attn``); jitted helpers (``jit(_where)``), control flow, einsum
    specifications and qualified function names are dropped."""
    while True:
        m = _WRAPPED.match(entry)
        if not m:
            break
        if m.group(1) == "jit":
            return None
        entry = m.group(2)
    if not entry or entry in _STRUCTURAL or "->" in entry or "<" in entry:
        return None
    return entry


def scope_path(op_name: str) -> str:
    """The program scopes of an operation's ``op_name`` metadata, outermost
    first: "jit(step_fn)/while/body/decode_pass/layers/while/body/
    closed_call/attn/kv_write/scatter" -> "decode_pass/layers/attn/kv_write"."""
    parts = [s for s in (_scope(e) for e in op_name.split("/")[:-1]) if s]
    return "/".join(parts) or NO_SCOPE


def _has(path: str, scope: str) -> bool:
    return scope in path.split("/")


def _varint(buf, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialised protobuf
    message; a length-delimited value is a view of its bytes."""
    pos = 0
    while pos < len(buf):
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace file")
        yield key >> 3, val


def _op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: op_name}} from the ``tf_op`` statistic
    that the device planes of an XSpace file keep in their event metadata
    (which ``ProfileData`` does not expose).  XPlane: name 2, event_metadata
    4, stat_metadata 5; XEventMetadata: name 2, display_name 4, stats 5;
    XStat: metadata_id 1, str_value 5, ref_value 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = bytes(v).decode()
            elif f2 in (4, 5):
                value = dict(_fields(v)).get(2, b"")
                if f2 == 4:
                    events.append(value)
                else:
                    meta = dict(_fields(value))
                    stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not trace._DEVICE.match(name):
            continue
        table = out.setdefault(name, {})
        for ev in events:
            meta = {}
            for f3, v in _fields(ev):
                if f3 in (2, 4):
                    meta.setdefault("names", []).append(bytes(v).decode())
                elif f3 == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        meta["op"] = (bytes(stat[5]).decode() if 5 in stat
                                      else stat_names.get(stat.get(7), ""))
            for n in meta.get("names", []):
                if "op" in meta:
                    table[n] = meta["op"]
    return out


def load(path: str) -> dict:
    """:func:`chipbench.trace.load`'s lists, and ``scopes`` ({chip: [scope
    path of each device event, in the same order]}) and ``spans`` ([(name,
    start, end, arguments)] of the program's host spans)."""
    from jax.profiler import ProfileData

    tr = trace.load(path)
    op_names = _op_names(path)
    scopes: Dict[int, List[str]] = {}
    spans = []
    for plane in ProfileData.from_file(path).planes:
        m = trace._DEVICE.match(plane.name)
        if m:
            line = next(ln for ln in plane.lines if ln.name == trace.OPS_LINE)
            table = op_names.get(plane.name, {})
            scopes[int(m.group(1))] = [scope_path(table.get(e.name, "")) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return dict(tr, scopes=scopes, spans=spans)


def _label(gap, spans) -> str:
    """The innermost span of either family open at the gap's middle."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for n, a, b in spans:
        if a <= mid < b and (best is None or b - a < best[2] - best[1]):
            best = (n, a, b)
    if best is None:
        return "no span"
    return best[0][len("chipbench."):] if best[0].startswith("chipbench.") else best[0]


def _pass_op(ops: dict) -> Optional[tuple]:
    """(scope path, operation) of what runs once a decode pass: the operation
    with the most self time under the decode pass's head (its matmul)."""
    head = {(p, op): t for p, by_op in ops.items()
            if _has(p, "decode_pass") and _has(p, "head") for op, (_, t) in by_op.items()}
    return max(head, key=head.get) if head else None


def reduce(tr: dict, top: int = 10) -> dict:
    """:func:`chipbench.trace.reduce`'s numbers, and the additions the module
    docstring lists."""
    out = trace.reduce(tr, top)
    lo, hi = next((a, b) for n, a, b in tr["host"] if n == trace.WINDOW_SPAN)
    spans = tr.get("spans", [])
    open_spans = [e for e in tr["host"] if e[0] != trace.WINDOW_SPAN]
    open_spans += [(n, a, b) for n, a, b, _ in spans]
    scopes, scope_ops, gaps, times = {}, {}, [], {}
    for chip, events in sorted(tr["devices"].items()):
        paths = tr["scopes"][chip]
        ev = [(i, max(a, lo), min(b, hi)) for i, (_, a, b) in enumerate(events)
              if b > lo and a < hi]
        table = defaultdict(float)
        ops = defaultdict(lambda: defaultdict(lambda: [0, 0.0]))
        for i, t in trace._self_times(ev):
            table[paths[i]] += t * 1e-9
            rec = ops[paths[i]][trace.op_name(events[i][0])]
            rec[0] += 1
            rec[1] += t * 1e-9
        scopes[chip] = dict(table)
        scope_ops[chip] = {p: dict(by_op) for p, by_op in ops.items()}
        busy = trace.union((a, b) for _, a, b in ev)
        gaps.extend(trace._subtract([(lo, hi)], busy))
        pass_op = _pass_op(scope_ops[chip])
        times[chip] = sorted(a for (name, a, _), p in zip(events, paths)
                             if (p, trace.op_name(name)) == pass_op)
    n_chips = max(1, len(scopes))
    total = defaultdict(float)
    for table in scopes.values():
        for p, t in table.items():
            total[p] += t / n_chips
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    out["breakdown"] = dict(
        out["breakdown"],
        device_scopes=[[p, t] for p, t in sorted(total.items(), key=lambda x: -x[1])[:top]],
        idle_gaps=[[_label(g, open_spans), (g[1] - g[0]) * 1e-9] for g in longest])
    out["scopes"] = scopes
    out["scope_ops"] = scope_ops
    out["spans"] = [[n, (a - lo) * 1e-9, (b - lo) * 1e-9, args] for n, a, b, args in spans]
    out["steps"] = _steps(spans, times, lo, hi)
    return out


def _steps(spans, times, lo, hi) -> List[dict]:
    """Host and device decode passes of each engine step wholly in the slice."""
    posts = [(a, b, args) for n, a, b, args in spans if n == "repro.serve.post"]
    out = []
    for n, a, b, _ in spans:
        if n != "repro.serve.step" or a < lo or b > hi:
            continue
        host = [args.get("passes") for pa, pb, args in posts if a <= pa and pb <= b]
        device = {chip: sum(a <= t < b for t in ts) for chip, ts in times.items()}
        out.append({"passes": host[0] if host else None, "device_passes": device})
    return out


def _share(tr: dict, pick) -> Optional[float]:
    """Share (%) of the slice's device busy time in operations whose scope
    path ``pick`` accepts."""
    busy = sum(c["busy_s"] for c in tr["chips"].values())
    t = sum(s for table in tr["scopes"].values() for p, s in table.items() if pick(p))
    return 100.0 * t / busy if t and busy else None


def decode_pass_ms(tr: Optional[dict]) -> Optional[float]:
    """Device self time under ``decode_pass`` over the decode passes the slice
    holds (events of the decode pass's head matmul), the mean over chips."""
    per_pass = []
    for chip, ops in (tr or {}).get("scope_ops", {}).items():
        pass_op = _pass_op(ops)
        if pass_op is not None:
            t = sum(s for p, s in tr["scopes"][chip].items() if _has(p, "decode_pass"))
            per_pass.append(1e3 * t / ops[pass_op[0]][pass_op[1]][0])
    return sum(per_pass) / len(per_pass) if per_pass else None


def _cache(path: str) -> bool:
    """The slot cache's own traffic: its write and read in attention, and the
    layer scan's slicing and stacking of the per-layer caches (operations
    whose innermost scope is ``layers``)."""
    return _has(path, "kv_write") or _has(path, "kv_read") or path.split("/")[-1] == "layers"


def kv_cache_share(tr: Optional[dict]) -> Optional[float]:
    """Share of device busy time in the slot cache's own traffic."""
    return _share(tr, _cache) if (tr or {}).get("scopes") else None


def serve_host_ms(tr: Optional[dict]) -> Optional[float]:
    """Mean, over the engine steps whose span ends in the slice, of the host
    work each adds beside waiting for the device: its admit, prep, dispatch
    and post spans."""
    spans = (tr or {}).get("spans", [])
    steps = [(a, b) for n, a, b, _ in spans
             if n == "repro.serve.step" and 0 <= b <= tr["window_s"]]
    if not steps:
        return None
    work = [(a, b) for n, a, b, _ in spans
            if n in {f"repro.serve.{w}" for w in HOST_WORK}]
    host = sum(b - a for sa, sb in steps for a, b in work if sa <= a and b <= sb)
    return 1e3 * host / len(steps)


def attn_share(tr: Optional[dict]) -> Optional[float]:
    """Share of device busy time under ``attn``: forward, recomputation and
    transpose alike."""
    if not (tr or {}).get("scopes"):
        return None
    return _share(tr, lambda p: _has(p, "attn"))


def readings(tr: dict) -> dict:
    """The per-layer numbers of one traced slice: the serving ones where the
    engine ran in it, else the training one."""
    if any(s[0] == "repro.serve.step" for s in tr["spans"]):
        values = {"decode_pass_ms": decode_pass_ms(tr),
                  "kv_cache_share.serve": kv_cache_share(tr),
                  "serve_host_ms": serve_host_ms(tr)}
    else:
        values = {"attn_share.train": attn_share(tr)}
    return {k: v for k, v in values.items() if v is not None}


def unscoped_share(tr: dict) -> Optional[float]:
    """Share of the slice's device self time under no program scope."""
    total = sum(sum(t.values()) for t in tr["scopes"].values())
    return sum(t.get(NO_SCOPE, 0.0) for t in tr["scopes"].values()) / total if total else None


def main(argv=None):
    """Run one cell with the profiler on (whatever ``--trace`` says), through
    this module's reduction."""
    import json

    import run as entry  # chipbench/run.py; its clock starts at its import

    args = entry.parse(argv)
    from chipbench import harness
    from repro import backend

    devices = entry.tpu_devices()
    if backend.target() != "tpu":
        raise SystemExit(f"scopes: backend target is {backend.target()!r}; unset REPRO_BACKEND")
    backend.enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.trace_mod = sys.modules[__name__]
    line, ctx = harness.run_cell(args.workload, args.seed, args.seconds, True, devices,
                                 entry.T0)
    harness.print_result(line)
    tr = ctx.trace_result
    print(json.dumps({"readings": readings(tr), "unscoped_share": unscoped_share(tr),
                      "steps": tr["steps"]}), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    main()
