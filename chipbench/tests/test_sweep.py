"""The knee sweep's readings of one window and of several pooled."""
import math

import numpy as np

from chipbench import sweep


def _window(at, first, last, tokens):
    return {"at": np.array(at), "first": np.array(first), "last": np.array(last),
            "tokens": np.array(tokens), "chunks": [1] * len(at), "slot_queue": 0,
            "work": [(0.5, []), (0.5, [])]}


def test_one_window():
    w = _window([1.0, 6.0, 9.0], [3.0, 8.0, 12.0], [4.0, 10.0, np.nan], [5, 9, 2])
    s = sweep.summary([w], 10.0, 16)
    assert s["requests"] == 3 and s["unfinished"] == 1
    assert s["in_flight_at_last_arrival"] == [2]  # the second and the unfinished third
    assert s["drain_s"] == [1.0]
    assert s["per_chunk_p90_first_third_ms"] == 2000.0  # due before 10/3 s
    assert s["per_chunk_p90_last_third_ms"] == 3000.0  # due from 20/3 s
    assert s["tpot_p90_ms"] is None  # the unfinished request is infinitely late
    assert not s["sustained"]


def test_windows_pool_their_requests():
    a = _window([1.0, 2.0], [2.0, 4.0], [3.0, 5.0], [3, 3])
    b = _window([1.0, 7.0], [1.5, 9.0], [2.0, 9.5], [2, 2])
    s = sweep.summary([a, b], 10.0, 16)
    assert s["requests"] == 4 and s["unfinished"] == 0
    assert s["drain_s"] == [3.0, 2.5]
    assert s["ttft_p50_ms"] == 1500.0  # of 0.5, 1.0, 2.0, 2.0 s
    assert math.isclose(s["tpot_p90_ms"], 500.0)  # of 0.5, 0.5, 0.5, 0.5 s
    assert s["step_p50_ms"] == 500.0
    # per chunk: first third 1.0, 2.0, 0.5 s; last third 2.0 s
    assert s["sustained"]
    b["slot_queue"] = 1  # a request waited for a slot
    assert not sweep.summary([a, b], 10.0, 16)["sustained"]
