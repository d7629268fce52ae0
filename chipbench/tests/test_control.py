"""The control, the reference in bfloat16 put in the program's place, must
fail at least one of each cell's numbers, as it does on the chip at the
cell's own size (``chipbench/calibrate.py --control``); here at a CPU's size
against the small cells' limits."""
import pytest

from chipbench.tests import small

# cell -> control readings, one of which must exceed its number's limit
CONTROLLED = {
    "smollm-360m.chat": ["kv_cache_err"],
    "smollm-360m.train": ["bf16.loss_gap", "bf16.grad1_gap", "bf16.update3_gap"],
    "qwen2-72b.prefill-tp4": ["logit_rel_err"],
}


@pytest.mark.parametrize("cell", sorted(CONTROLLED))
def test_control_fails_a_number(cell):
    line, ctx = small.run(cell, control=True)
    assert line["correct"]  # the program itself passes
    limits = {k: c["limit"] for k, c in line["checks"].items()}
    failed = [k for k in CONTROLLED[cell]
              if ctx.control_readings[k] > limits[k.split(".", 1)[-1]]]
    assert failed, (ctx.control_readings, limits)
