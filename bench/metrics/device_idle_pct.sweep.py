"""device_idle_pct.sweep: the share of the traced window in which no
operation ran, averaged over the cell's chips."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share()
