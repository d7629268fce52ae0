"""Mean host time of one ServeEngine.step() (which ends in its one fetch)."""


def read(record):
    s = record.get("serve")
    if not s or not s["steps"]:
        return None
    return 1e3 * sum(dt for dt, _ in s["steps"]) / len(s["steps"])
