"""What every cell shares: finding its files by name, the clock, the trace,
the checks, and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its configuration
is ``configs/<config>.json`` (which names the model family's plain reference,
``references/<family>.py``), its traffic ``traffic/<traffic>.json`` (which
names the driver), its driver ``drivers/<driver>.py`` and each metric
``metrics/<metric>.py``.  A driver's ``run(ctx)`` does the set-up, calls
``ctx.open_window()``, drives the program for ``ctx.seconds``, calls
``ctx.close_window()`` and then checks what the window produced with
``ctx.check``; it returns the record the metric readers read.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from chipbench import trace as trace_mod  # noqa: E402
from chipbench.flops import Dims  # noqa: E402

__all__ = ["Context", "load_cell", "run_cell", "result_line", "TRACE_SECONDS"]

TRACE_SECONDS = 3.0  # length of the traced slice of a --trace 1 window
# the profiler starts this long before the slice opens: its start-up stalls the
# host and its device tracing comes up late, and neither belongs in the slice
TRACE_LEAD_S = 2.0
TRACE_DIR = HERE / ".trace"

def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}".replace(".", "_")
                                                  .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file
    mix: dict  # the traffic file
    end_to_end: List[dict]  # metric entries of BENCHMARK.json this cell reports
    per_layer: List[dict]

    @property
    def reference(self):
        """The plain reference of the configuration's family:
        ``references/<family>.py``, as the configuration file names it."""
        return importlib.import_module(f"chipbench.references.{self.config['reference']}")

    @property
    def dims(self) -> Dims:
        return self.reference.dims(self.config["model"])


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else _json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(ROOT / configs[w["config"]]["file"])
    mix = _json(HERE / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per)


def program_config(cell: Cell):
    """The program's ArchConfig as the configuration file states it.

    Every key of the file's ``model`` block that the program has (the
    family's ``ARCH_FIELDS``) is set from the file, and must equal the
    program's registered value unless the file lists it under ``reduced`` or
    ``set``."""
    from repro.configs import get_config

    config, keys = cell.config, cell.reference.ARCH_FIELDS
    base = get_config(config["program_arch"])
    model = config["model"]
    fields = {keys[k]: v for k, v in model.items() if k in keys}
    changed = set(config.get("reduced", {})) | set(config.get("set", {}))
    for k, v in model.items():
        f = keys.get(k)
        if f and k not in changed and getattr(base, f) != v:
            raise ValueError(f"{config['name']}: program's {f}={getattr(base, f)!r} "
                             f"but the file says {k}={v!r}")
    return dataclasses.replace(base, **fields)


class Context:
    """One run of one cell: what the driver needs and what it leaves behind."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool, devices,
                 t0: float):
        self.cell, self.seed, self.seconds, self.trace = cell, int(seed), float(seconds), trace
        self.devices = devices
        self.t0 = t0
        self.mix = cell.mix
        self.dims = cell.dims
        self.reference = cell.reference
        self.arch = program_config(cell)
        self.checks: Dict[str, dict] = {}
        self.setup_s = None
        self.window = None  # (open, close) on the host clock
        self.memory_peak_bytes = None
        self.window_compiles = 0
        self._in_window = False
        self._trace_state = None  # None, "started" (profiler on), "on" (slice open), "done"
        self._annot = None
        self._listener_installed = False
        self.trace_result = None
        self.control = False  # also read the control's numbers (calibration only)
        self.control_readings: Dict[str, float] = {}

    # -- clock and spans -------------------------------------------------------
    @staticmethod
    def now() -> float:
        return time.perf_counter()

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (cheap when nothing traces)."""
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    def _on_event(self, event: str, *args, **kwargs):
        if self._in_window and ("compile" in event or "trace_duration" in event):
            self.window_compiles += 1

    def open_window(self):
        if not self._listener_installed:
            jax.monitoring.register_event_duration_secs_listener(self._on_event)
            self._listener_installed = True
        t = self.now()
        self.setup_s = t - self.t0
        self.window = (t, None)
        self._in_window = True
        self.tick(t)
        return t

    def close_window(self):
        t = self.now()
        self._in_window = False
        self.window = (self.window[0], t)
        if self._trace_state == "started":  # the window closed before the slice opened
            self._annot = self.span("window")
            self._annot.__enter__()
        if self._trace_state in ("started", "on"):
            self._stop_trace()
        return t

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    # -- the traced slice ---------------------------------------------------------
    def trace_slice(self):
        """(start, end) of the traced slice, seconds after the window opens."""
        length = min(TRACE_SECONDS, self.seconds)
        start = max(0.0, self.seconds / 2 - length / 2)
        return start, start + length

    def tick(self, t: Optional[float] = None):
        """Called by the driver between its calls: starts the profiler
        ``TRACE_LEAD_S`` before the traced slice, and opens and closes the
        slice's span at its ends."""
        if not self.trace or self._trace_state == "done" or self.window is None:
            return
        t = self.now() if t is None else t
        start, end = self.trace_slice()
        rel = t - self.window[0]
        if self._trace_state is None and rel >= start - TRACE_LEAD_S:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self._trace_state = "started"
        if self._trace_state == "started" and rel >= start:
            self._annot = self.span("window")
            self._annot.__enter__()
            self._trace_state = "on"
        elif self._trace_state == "on" and rel >= end:
            self._stop_trace()

    def _stop_trace(self):
        self._annot.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._trace_state = "done"

    def reduce_trace(self):
        if not self.trace:
            return None
        if self._trace_state != "done":
            raise RuntimeError("the traced slice never ran")
        files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no xplane file")
        try:
            self.trace_result = trace_mod.reduce(trace_mod.load(str(files[-1])))
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return self.trace_result

    # -- device ---------------------------------------------------------------------
    def read_memory(self):
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None
        return self.memory_peak_bytes

    def mesh(self):
        """The program's ("pod", "data", "model") mesh, tensor parallel over
        this cell's chips."""
        import numpy as np
        from jax.sharding import Mesh

        return Mesh(np.asarray(self.devices).reshape(1, 1, len(self.devices)),
                    ("pod", "data", "model"))

    def weights(self, pc):
        """The program's parameter tree, every leaf from the seed, made on the
        device in one jitted call with the program's shardings."""
        from jax.sharding import NamedSharding

        from chipbench import weights
        from repro.models import lm

        reference = self.reference
        shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), self.arch, pc,
                                                jax.numpy.float32))
        got = {weights.path_name(p): tuple(s.shape)
               for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]}
        want = reference.tree_shapes(self.dims, pc.tp, bool(self.arch.tie_embeddings))
        if got != want:
            raise ValueError(f"the program's parameter tree is not the layout the reference "
                             f"reads: program {got}, reference {want}")
        shard = jax.tree_util.tree_map(lambda s: NamedSharding(pc.mesh, s),
                                       lm.specs(self.arch, pc),
                                       is_leaf=lambda v: isinstance(v, jax.sharding.PartitionSpec))
        key = weights.root_key(self.seed)
        make = jax.jit(lambda k: weights.build(k, shapes, stacked=reference.stacked),
                       out_shardings=shard)
        return make(key)

    # -- checks ------------------------------------------------------------------------
    def check(self, name: str, value: float, limit: float):
        """A number compared with its limit; the run is correct only if every
        one is at or under its limit."""
        self.checks[name] = {"value": float(value), "limit": float(limit)}


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def result_line(ctx: Context, record: dict, attempted: int, failed: int) -> dict:
    """The last line of the run: metrics by the readers of BENCHMARK.json's
    entries, the device, the breakdown of a traced run, and the checks."""
    cell = ctx.cell
    record = dict(record, setup_s=ctx.setup_s, window_s=ctx.window_s, dims=ctx.dims,
                  trace=ctx.trace_result, peaks_kind=ctx.devices[0].device_kind,
                  chips=len(ctx.devices))
    entries = cell.per_layer if ctx.trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = _module(HERE / "metrics" / f"{m['name']}.py", m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = device_info(ctx.devices)
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    if ctx.trace:
        chips = ctx.trace_result["chips"]
        device["busy_s"] = sum(c["busy_s"] for c in chips.values()) / len(chips)
        device["window_s"] = ctx.trace_result["window_s"]
    checks = dict(ctx.checks)
    checks["window_compiles"] = {"value": float(ctx.window_compiles), "limit": 0.0}
    correct = bool(ctx.checks) and all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if ctx.trace:
        line["breakdown"] = ctx.trace_result["breakdown"]
    line["checks"] = checks
    return line


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices, t0: float,
             bench: Optional[dict] = None, edit: Optional[Callable[[Cell], None]] = None,
             control: bool = False):
    """Run one cell on ``devices``; returns the result line and the run's
    context.  ``edit`` may change the loaded cell (tests shrink it to a CPU's
    size); ``control`` also reads the control's numbers (calibration)."""
    cell = load_cell(name, bench)
    if edit is not None:
        edit(cell)
    if len(devices) < cell.chips:
        raise SystemExit(f"{name} needs {cell.chips} chip(s), JAX sees {len(devices)}")
    devices = list(devices)[: cell.chips]
    driver = _module(HERE / "drivers" / f"{cell.mix['driver']}.py", cell.mix["driver"])
    ctx = Context(cell, seed, seconds, trace, devices, t0)
    ctx.control = control
    record = driver.run(ctx)
    if ctx.window is None or ctx.window[1] is None:
        raise RuntimeError(f"driver {cell.mix['driver']} never closed its window")
    ctx.reduce_trace()
    return result_line(ctx, record, record.pop("attempted"), record.pop("failed")), ctx


def print_result(line: dict):
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
