"""Forward and backward FLOPs per step (3 x (2N + causal attention), no
recomputation) times steps completed, over the window times chips times the
bf16 peak."""
from chipbench.flops import train_flops
from chipbench.hw import peaks


def read(record):
    t = record.get("train")
    if not t or not t["steps"]:
        return None
    f = train_flops(record["dims"], t["seq"], t["batch"]) * t["steps"]
    return 100.0 * f / (record["window_s"] * record["chips"]
                        * peaks(record["peaks_kind"]).flops)
