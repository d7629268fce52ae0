"""The scope reduction (``chipbench/scopes.py``) on constructed traces, and on
the CPU's own trace of a small serving and training run."""
import pytest

from chipbench import harness, scopes
from chipbench.tests import small

MS = 1_000_000  # ns


def op(name, a, b):
    return (f"%{name} = f32[8]{{0}} fusion()", a * MS, b * MS)


def span(name, a, b, **args):
    return (name, a * MS, b * MS, args)


def trace(ops, paths, spans=(), host=()):
    return {"devices": {0: ops}, "scopes": {0: paths}, "spans": list(spans),
            "host": [("chipbench.window", 0, 100 * MS)] + list(host)}


@pytest.mark.parametrize("name,want", [
    ("jit(step_fn)/while/body/decode_pass/layers/while/body/closed_call/attn/kv_write/"
     "vmap()/scatter", "decode_pass/layers/attn/kv_write"),
    ("jit(train_step)/loss/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/bhqd,bhkd->bhqk/dot_general", "loss/layers/attn"),
    ("jit(step_fn)/sample/vmap(jit(_gumbel))/jit(_uniform)/vmap()/shift_right_logical",
     "sample"),
    ("jit(f)/layers/while/body/closed_call/mlp/overlap.matmul_rs/ppermute",
     "layers/mlp/overlap.matmul_rs"),
    ("jit(step_fn)/while/body/ServeEngine._build_step.<locals>.step_fn/add", scopes.NO_SCOPE),
    ("", scopes.NO_SCOPE),
])
def test_scope_path_keeps_program_scopes_only(name, want):
    assert scopes.scope_path(name) == want


def test_self_time_goes_to_the_innermost_scope():
    # the decode loop 0-50 holds a layer 10-40, which holds a kv write 20-25
    tr = trace([op("while.1", 0, 50), op("fusion.1", 10, 40), op("fusion.2", 20, 25)],
               [scopes.NO_SCOPE, "decode_pass/layers/attn", "decode_pass/layers/attn/kv_write"])
    r = scopes.reduce(tr)
    assert r["scopes"][0] == pytest.approx({
        scopes.NO_SCOPE: 0.020, "decode_pass/layers/attn": 0.025,
        "decode_pass/layers/attn/kv_write": 0.005})
    assert r["breakdown"]["device_scopes"][0] == ["decode_pass/layers/attn",
                                                  pytest.approx(0.025)]
    assert scopes.unscoped_share(r) == pytest.approx(0.4)


def _serving_slice():
    """Two engine steps: the first (2-50) runs 3 decode passes, the second
    (52-120) runs 2, of which one lies past the slice's end at 100."""
    ops, paths = [], []
    for a in (10, 20, 30, 60, 105):  # one pass: a layer with its cache write, then the head
        ops += [op("fusion.1", a, a + 4), op("fusion.2", a + 4, a + 6),
                op("fusion.3", a + 6, a + 8)]
        paths += ["decode_pass/layers", "decode_pass/layers/attn/kv_write",
                  "decode_pass/head"]
    ops += [op("fusion.9", 8, 9), op("fusion.8", 55, 59)]  # the head's bias, the mixed pass
    paths += ["decode_pass/head", "mixed_pass/layers/attn"]
    spans = [span("repro.serve.step", 2, 50), span("repro.serve.admit", 2, 3),
             span("repro.serve.prep", 3, 8), span("repro.serve.dispatch", 8, 9),
             span("repro.serve.fetch", 9, 38), span("repro.serve.post", 38, 49, passes=3),
             span("repro.serve.step", 52, 120), span("repro.serve.prep", 52, 53),
             span("repro.serve.dispatch", 53, 54), span("repro.serve.fetch", 54, 110),
             span("repro.serve.post", 110, 111, passes=2)]
    return trace(ops, paths, spans, host=[("chipbench.serve.step", 1 * MS, 51 * MS)])


def test_decode_passes_are_counted_on_the_device_and_checked_against_the_engine():
    r = scopes.reduce(_serving_slice())
    # the head matmul (fusion.3, 2 ms a pass) ran 4 times in the slice
    assert r["scope_ops"][0]["decode_pass/head"]["fusion.3"] == [4, pytest.approx(0.008)]
    # 4 passes, each 4 + 2 + 2 ms, and the bias op once (1 ms)
    assert scopes.decode_pass_ms(r) == pytest.approx(33 / 4)
    # only the first step lies wholly in the slice; it ran 3 passes on both counts
    assert r["steps"] == [{"passes": 3, "device_passes": {0: 3}}]


def test_cache_share_counts_the_write_read_and_the_scan_s_own_slicing():
    r = scopes.reduce(_serving_slice())
    busy = r["chips"][0]["busy_s"]
    assert busy == pytest.approx(0.037)
    assert scopes.kv_cache_share(r) == pytest.approx(100 * 0.024 / 0.037)


def test_serve_host_ms_sums_a_step_s_host_work_beside_its_fetch():
    r = scopes.reduce(_serving_slice())
    # the step ending in the slice: admit 1 + prep 5 + dispatch 1 + post 11 ms
    assert scopes.serve_host_ms(r) == pytest.approx(18.0)
    assert scopes.readings(r) == {"decode_pass_ms": scopes.decode_pass_ms(r),
                                  "kv_cache_share.serve": scopes.kv_cache_share(r),
                                  "serve_host_ms": pytest.approx(18.0)}
    assert r["spans"][0] == ["repro.serve.step", pytest.approx(0.002), pytest.approx(0.050), {}]


def test_idle_gaps_take_the_innermost_span_of_either_family():
    r = scopes.reduce(_serving_slice())
    labels = [name for name, _ in r["breakdown"]["idle_gaps"]]
    # 0-8 falls in prep and 38-55 mostly in post, both inside the harness's
    # serve.step span; the other gaps wait in the engine's fetch
    assert labels[:2] == ["repro.serve.fetch", "repro.serve.post"]  # 68-100, 38-55
    assert set(labels) == {"repro.serve.prep", "repro.serve.fetch", "repro.serve.post"}


def test_attention_share_counts_forward_recompute_and_transpose():
    tr = trace([op("fusion.1", 0, 30), op("fusion.2", 30, 40), op("fusion.3", 40, 50)],
               ["loss/layers/attn", "loss/layers/mlp", "optimizer"])
    r = scopes.reduce(tr)
    assert scopes.attn_share(r) == pytest.approx(60.0)
    assert scopes.readings(r) == {"attn_share.train": pytest.approx(60.0)}


def test_the_existing_numbers_are_unchanged():
    from chipbench import trace as base

    tr = _serving_slice()
    want, got = base.reduce(tr), scopes.reduce(tr)
    assert got["window_s"] == want["window_s"]
    assert got["chips"] == want["chips"]
    assert got["breakdown"]["device_ops"] == want["breakdown"]["device_ops"]


def test_load_reads_op_names_from_event_metadata(tmp_path):
    from jax.profiler import ProfileData

    text = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion()"
    stats { metadata_id: 1 str_value: "jit(f)/while/body/decode_pass/attn/dot_general" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8]{0} copy()"
    stats { metadata_id: 1 ref_value: 3 } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "jit(f)/layers/while/body/dynamic_slice" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000
             stats { metadata_id: 1 int64_value: 4 } } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "repro.serve.post" } }
  stat_metadata { key: 1 value { id: 1 name: "passes" } } }
'''
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    tr = scopes.load(str(path))
    assert tr["scopes"] == {0: ["decode_pass/attn", "layers"]}
    assert tr["spans"] == [("repro.serve.post", 0, 1, {"passes": 4})]
    assert tr["host"] == [("chipbench.window", 0, 9000)]


# ---- through the harness, on the CPU's own trace -----------------------------

def _cpu_load(path):
    """The CPU has no TPU plane: its XLA threads stand in for chip 0, whose
    operations carry no op_name (so no scope)."""
    from jax.profiler import ProfileData

    host, ops, spans = [], [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("chipbench."):
                    host.append((e.name, e.start_ns, e.end_ns))
                elif e.name.startswith(scopes.SPAN_PREFIX):
                    spans.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
                elif "XLA" in line.name:
                    ops.append((e.name, e.start_ns, e.end_ns))
    return {"devices": {0: ops}, "host": host, "spans": spans,
            "scopes": {0: [scopes.NO_SCOPE] * len(ops)}}


@pytest.mark.parametrize("cell", ["smollm-360m.chat", "smollm-360m.train"])
def test_traced_run_through_the_scope_reduction(monkeypatch, cell):
    from chipbench import hw

    monkeypatch.setattr(harness, "trace_mod", scopes)
    monkeypatch.setattr(scopes, "load", _cpu_load)
    # the utilizations divide by a chip's peaks; this CPU run borrows the v5e's
    monkeypatch.setitem(hw.PEAKS, "cpu", hw.PEAKS["TPU v5 lite"])
    line, ctx = small.run(cell, trace=True)
    assert line["correct"]
    tr = ctx.trace_result
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps", "device_scopes"}
    if cell == "smollm-360m.chat":
        steps = [s for s in tr["spans"] if s[0] == "repro.serve.step"]
        assert steps and all(s[0].startswith("repro.serve.") for s in tr["spans"])
        assert scopes.serve_host_ms(tr) > 0
        posts = [s[3] for s in tr["spans"] if s[0] == "repro.serve.post"]
        assert posts and set(posts[0]) == {"passes", "prefill_tokens", "decode_tokens",
                                           "slots_busy", "queued"}
    else:
        assert not tr["spans"] and scopes.serve_host_ms(tr) is None
