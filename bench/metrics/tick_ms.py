"""tick_ms: the window over its whole ticks, host arrays in to placement
and σ on the host out (host clock)."""


def read(run):
    return 1e3 * run.window_s / run.steps
