"""The reduction from a trace to the per-layer numbers, on constructed traces."""
import pytest

from chipbench import trace

MS = 1_000_000  # ns


def op(name, a, b, shape="f32[8]{0}", kind="fusion"):
    return (f"%{name} = {shape} {kind}(f32[8]{{0}} %all-gather-start.9)", a * MS, b * MS)


def window(a, b):
    return ("chipbench.window", a * MS, b * MS)


def test_busy_idle_and_exposed_collectives():
    # chip 0: compute 0-4, a collective 3-6 (exposed 4-6), idle 6-8, compute 8-10
    # chip 1: compute 0-10 with a collective 2-3 hidden under it
    tr = {
        "devices": {
            0: [op("fusion.1", 0, 4), op("all-gather-start.3", 3, 6, kind="all-gather-start"),
                op("fusion.2", 8, 10)],
            1: [op("fusion.1", 0, 10), op("collective-permute-done.1", 2, 3,
                                          kind="collective-permute-done")],
        },
        "host": [window(0, 10), ("chipbench.serve.step", 5 * MS, 9 * MS)],
    }
    r = trace.reduce(tr)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["chips"][0]["busy_s"] == pytest.approx(0.008)
    assert r["chips"][0]["exposed_comm_s"] == pytest.approx(0.002)
    assert r["chips"][1]["busy_s"] == pytest.approx(0.010)
    assert r["chips"][1]["exposed_comm_s"] == 0
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps == [["serve.step", pytest.approx(0.002)]]


def test_operand_names_do_not_make_a_collective():
    # the text of a fusion names an all-gather operand; the fusion is compute
    assert not trace.is_collective(op("fusion.7", 0, 1)[0])
    assert trace.is_collective(op("all-reduce.2", 0, 1, kind="all-reduce")[0])
    assert trace.is_collective(op("reduce-scatter.1", 0, 1, kind="reduce-scatter")[0])
    assert trace.op_name(op("fusion.7", 0, 1)[0]) == "fusion.7"
    assert trace.op_label(op("fusion.7", 0, 1)[0]) == "fusion.7 f32[8]{0}"


def test_top_ops_use_self_time_of_nested_events():
    # a loop 0-10 holds two fusions; the loop itself keeps only 2 ms
    tr = {"devices": {0: [op("while.1", 0, 10, kind="while"), op("fusion.1", 1, 6),
                          op("fusion.2", 6, 9)]},
          "host": [window(0, 10)]}
    ops = dict(trace.reduce(tr)["breakdown"]["device_ops"])
    assert ops["fusion.1 f32[8]{0}"] == pytest.approx(0.005)
    assert ops["fusion.2 f32[8]{0}"] == pytest.approx(0.003)
    assert ops["while.1 f32[8]{0}"] == pytest.approx(0.002)


def test_window_clips_and_gaps_take_innermost_span():
    tr = {"devices": {0: [op("fusion.1", -5, 2), op("fusion.2", 7, 20)]},
          "host": [window(0, 10), ("chipbench.serve.step", 0, 10 * MS),
                   ("chipbench.serve.fetch", 3 * MS, 6 * MS)]}
    r = trace.reduce(tr)
    assert r["chips"][0]["busy_s"] == pytest.approx(0.005)
    assert r["breakdown"]["idle_gaps"] == [["serve.fetch", pytest.approx(0.005)]]


def test_window_span_is_required():
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.reduce({"devices": {0: []}, "host": []})


def test_load_reads_device_planes_and_host_spans(tmp_path):
    from jax.profiler import ProfileData

    text = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion()" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "other" } } }
'''
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    tr = trace.load(str(path))
    assert tr["host"] == [("chipbench.window", 0, 5000)]
    assert tr["devices"] == {0: [("%fusion.1 = f32[8]{0} fusion()", 1000, 3000)]}
    r = trace.reduce(tr)
    assert r["chips"][0]["busy_s"] == pytest.approx(2e-6)
