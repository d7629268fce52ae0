"""The FLOP and byte counts against hand counts."""
import json
import pathlib

import pytest

from chipbench import flops

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return flops.dims_of(json.loads((CONFIGS / f"{name}.json").read_text())["model"])


def test_smollm_params_by_hand():
    dm = dims("smollm-360m")
    # q 960x960, k and v 960x320 each, o 960x960; gate, up, down 960x2560
    layer = 960 * 960 * 2 + 960 * 320 * 2 + 3 * 960 * 2560
    assert dm.layer_matmul_params == layer == 9_830_400
    assert dm.matmul_params == 32 * layer + 960 * 49152  # tied head counts once
    assert dm.kv_row_bytes == 32 * 5 * 64 * 2 * 4


def test_qwen2_forward_by_hand():
    dm = dims("qwen2-72b")
    layer = 8192 * 8192 * 2 + 8192 * 1024 * 2 + 3 * 8192 * 29568
    assert dm.layer_matmul_params == layer == 877_658_112
    n = 8 * layer + 8192 * 152064
    attn = 4 * 8 * 64 * 128 * 8192 * (8192 + 1) / 2
    assert flops.forward_flops(dm, 8192) == pytest.approx(2 * n * 8192 + attn, rel=1e-12)
    assert flops.forward_flops(dm, 8192) == pytest.approx(1.442e14, rel=2e-3)


def test_train_is_three_forwards():
    dm = dims("smollm-360m")
    f = flops.train_flops(dm, 1024, 8)
    assert f == pytest.approx(3 * flops.forward_flops(dm, 1024, 8))
    # about 2.36 GFLOP per token
    assert f / (8 * 1024) == pytest.approx(2.36e9, rel=0.01)


def test_serve_step_counts_each_pass():
    dm = dims("smollm-360m")
    # slot A prefills 16 tokens after 32 cached, emits nothing; slot B decodes
    # its pending token after 100 cached and emits 3 tokens
    work = [flops.SlotWork(32, 16, 0), flops.SlotWork(100, 1, 3)]
    passes = flops.serve_step(dm, work)
    assert len(passes) == 3
    weights = 4 * (32 * dm.layer_params + dm.head_params + dm.d)
    f0, b0 = passes[0]
    assert f0 == pytest.approx(2 * 32 * dm.layer_matmul_params * 17 + 2 * dm.head_params
                               + dm.attn_flops(16, 32 + 8.5) + dm.attn_flops(1, 101))
    assert b0 == weights + (48 + 101) * dm.kv_row_bytes
    f2, b2 = passes[2]  # the second decode pass: slot B alone, 102 keys cached
    assert f2 == pytest.approx(2 * 32 * dm.layer_matmul_params + 2 * dm.head_params
                               + dm.attn_flops(1, 103))
    assert b2 == weights + 103 * dm.kv_row_bytes


def test_idle_step_has_no_work():
    assert flops.serve_step(dims("smollm-360m"), []) == [(0.0, 0.0)]
