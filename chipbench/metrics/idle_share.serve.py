"""1 - (union of the device's operation intervals / traced window), the mean
over the chips used (device trace)."""
from chipbench.readings import idle_share


def read(record):
    v = idle_share(record)
    return None if v is None else 100.0 * v
