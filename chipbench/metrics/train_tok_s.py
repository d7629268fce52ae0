"""Training tokens of every step completed in the window over the window."""


def read(record):
    t = record.get("train")
    return None if t is None else t["tokens"] / record["window_s"]
