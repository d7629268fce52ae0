"""FLOPs the serving steps' work needs (tokens fed, keys attended up to each
slot's length, the head for sampled rows) over the steps' host time times the
chip's bf16 peak."""
from chipbench.hw import peaks


def read(record):
    s = record.get("serve")
    if not s or not s["steps"]:
        return None
    t = sum(dt for dt, _ in s["steps"])
    f = sum(fl for _, passes in s["steps"] for fl, _ in passes)
    if f <= 0 or t <= 0:
        return None
    return 100.0 * f / (t * peaks(record["peaks_kind"]).flops)
