"""CPU rehearsal of ``chip_smoke.py``: its serve, train and TP-forward phases
run here on a reduced smollm-360m with the same assertions, so the script
cannot rot between chip runs; its ``main`` must still refuse a host with no
TPU."""
import importlib.util
import json
import pathlib

import pytest

from repro import backend
from repro.configs import get_config
from repro.launch.train import reduce_config

_PATH = pathlib.Path(__file__).resolve().parent.parent / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

AXES = ("pod", "data", "model")


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(get_config(chip_smoke.ARCH))


def test_serve_phase(cfg):
    # more requests than slots: queued requests are admitted mid-run
    res = chip_smoke.phase_serve(cfg, backend.make_mesh((1, 1, 1), AXES), n_requests=6,
                                 prompt_len=24, new_tokens=12, slots=4)
    assert [len(t) for t in res["tokens"]] == [12] * 6
    assert res["stats"]["step_traces"] == 1


def test_train_phase(cfg):
    hist = chip_smoke.phase_train(cfg, steps=3, batch=8, seq=64)
    assert len(hist) == 3


def test_tp_phase(cfg):
    err = chip_smoke.phase_tp(cfg, backend.make_mesh((1, 1, 4), AXES), batch=2, seq=64)
    assert err <= chip_smoke.TP_TOL


def test_fused_phase(cfg, capsys):
    # both kernels run in the interpreter and match the XLA executor; the
    # phase then refuses, since nothing was compiled by Mosaic
    import jax

    with pytest.raises(chip_smoke.SmokeFailure, match="not Mosaic"):
        chip_smoke.phase_fused(jax.devices()[:4], cfg, tokens=64)
    out = capsys.readouterr().out
    assert "fused ag_matmul" in out and "fused matmul_rs" in out


def test_check_raises_on_failure():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main([])
    assert ei.value.code not in (0, None)
    assert "no TPU" in str(ei.value.code)
    out = capsys.readouterr().out
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.splitlines())
