"""The serving steps' share of their roofline: for each forward pass of a
step the least time is max(FLOPs / peak, bytes / HBM bandwidth), weights
read once a pass and cached keys and values up to each slot's length; the
sum of those over the sum of the steps' host time."""
from chipbench.hw import peaks


def read(record):
    s = record.get("serve")
    if not s or not s["steps"]:
        return None
    pk = peaks(record["peaks_kind"])
    t = sum(dt for dt, _ in s["steps"])
    least = sum(max(fl / pk.flops, by / pk.hbm_bytes_s)
                for _, passes in s["steps"] for fl, by in passes)
    if least <= 0 or t <= 0:
        return None
    return 100.0 * least / t
