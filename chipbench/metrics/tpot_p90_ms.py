"""90th percentile over requests of (last token - first token) / (tokens - 1)
(host clock; tokens arrive in blocks, one host sync per engine step)."""
import numpy as np

from chipbench.readings import percentile


def read(record):
    s = record.get("serve")
    if s is None:
        return None
    n = s["tokens"].astype(float)
    per = np.where(n > 1, (s["last"] - s["first"]) / np.maximum(n - 1, 1), np.nan)
    v = percentile(per, 90)
    return None if v is None else 1e3 * v
