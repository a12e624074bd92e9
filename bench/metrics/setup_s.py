"""setup_s: process start to the window's start, compiles, input draws
and warm-up included (host clock)."""


def read(run):
    return run.setup_s
