"""Set-up: process start to the window's opening (host clock)."""


def read(record):
    return record["setup_s"]
