"""The traffic generator: a seed orders a fixed amount of work."""
import numpy as np

from chipbench import traffic

MIX = {"arrivals": "poisson", "rate_per_s": 4.0,
       "prompt": {"median": 256, "sigma": 0.7, "min": 32, "max": 1024},
       "output": {"median": 64, "sigma": 0.7, "min": 16, "max": 512}}
BIG = 2**40 + 12345  # seeds are any whole number, beyond 32 bits


def test_same_seed_same_schedule():
    a = traffic.open_loop(MIX, BIG, 30, 49152)
    b = traffic.open_loop(MIX, BIG, 30, 49152)
    assert [(r.at, r.out_len) for r in a] == [(r.at, r.out_len) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_seeds_reorder_the_same_work():
    a = traffic.open_loop(MIX, 1, 30, 49152)
    b = traffic.open_loop(MIX, BIG, 30, 49152)
    assert len(a) == len(b) == 120
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.out_len for r in a) == sorted(r.out_len for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_lengths_within_clips_and_window():
    reqs = traffic.open_loop(MIX, 9, 30, 49152)
    assert all(32 <= len(r.prompt) <= 1024 and 16 <= r.out_len <= 512 for r in reqs)
    assert all(0 < r.at < 30 for r in reqs)
    assert [r.at for r in reqs] == sorted(r.at for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 49152 for r in reqs)
    lens = sorted(len(r.prompt) for r in reqs)
    assert lens[len(lens) // 2] in range(240, 272)  # the median is the mix's


def test_token_rows_depend_on_seed_and_index():
    a = traffic.token_rows(BIG, 3, 2, 5, 100)
    assert (a == traffic.token_rows(BIG, 3, 2, 5, 100)).all()
    assert not (a == traffic.token_rows(BIG, 4, 2, 5, 100)).all()
    assert a.shape == (2, 5) and a.max() < 100
