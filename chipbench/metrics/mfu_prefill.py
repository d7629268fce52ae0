"""Forward FLOPs per prompt (2N + causal attention) times forwards completed,
over the window times chips times the bf16 peak."""
from chipbench.flops import forward_flops
from chipbench.hw import peaks


def read(record):
    p = record.get("prefill")
    if not p or not p["forwards"]:
        return None
    f = forward_flops(record["dims"], p["seq"], p["batch"]) * p["forwards"]
    return 100.0 * f / (record["window_s"] * record["chips"]
                        * peaks(record["peaks_kind"]).flops)
