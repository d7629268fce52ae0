"""Share of the traced window in which a collective runs on a chip and no
other operation does; the largest over the chips (device trace)."""


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    return 100.0 * max(c["exposed_comm_s"] for c in tr["chips"].values()) / tr["window_s"]
