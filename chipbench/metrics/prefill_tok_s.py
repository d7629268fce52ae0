"""Prompt tokens of every forward completed in the window over the window."""


def read(record):
    p = record.get("prefill")
    return None if p is None else p["tokens"] / record["window_s"]
