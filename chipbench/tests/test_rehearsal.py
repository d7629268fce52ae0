"""Each driver end to end at a CPU's size: one device for serving and
training, four virtual devices for the TP prefill.  Every run must print a
well-formed last line and check out correct."""
import json

import pytest

from chipbench import harness
from chipbench.tests import small

CELLS = ["smollm-360m.chat", "smollm-360m.train", "qwen2-72b.prefill-tp4"]


def well_formed(line, cell, traced):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(line)[-1] == "checks"
    json.loads(json.dumps(line))  # plain JSON, no NaN or infinity
    c = harness.load_cell(cell, small.bench())
    want = {m["name"] for m in (c.per_layer if traced else c.end_to_end)}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert line["device"]["count"] == c.chips
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(cell):
    line, ctx = small.run(cell)
    well_formed(line, cell, traced=False)
    assert line["correct"], line["checks"]
    assert line["checks"]["window_compiles"]["value"] == 0
    assert 0 < line["metrics"]["setup_s"]["value"] < ctx.window[0] - ctx.t0 + 1


def _cpu_trace_load(path):
    """The CPU has no TPU plane: its XLA threads stand in for chip 0."""
    from jax.profiler import ProfileData

    host, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("chipbench."):
                    host.append((e.name, e.start_ns, e.end_ns))
                elif "XLA" in line.name:
                    ops.append((e.name, e.start_ns, e.end_ns))
    return {"devices": {0: ops}, "host": host}


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    from chipbench import hw

    monkeypatch.setattr("chipbench.trace.load", _cpu_trace_load)
    # the utilizations divide by a chip's peaks; this CPU run borrows the v5e's
    monkeypatch.setitem(hw.PEAKS, "cpu", hw.PEAKS["TPU v5 lite"])
    line, _ = small.run("smollm-360m.train", trace=True)
    well_formed(line, "smollm-360m.train", traced=True)
    assert line["correct"]
    assert 0 < line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not (harness.TRACE_DIR).exists()
