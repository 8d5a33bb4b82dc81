"""setup_s: process start to window open, host clock (seconds)."""


def read(run):
    return run.setup_s
