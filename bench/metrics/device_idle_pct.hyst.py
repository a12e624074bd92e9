"""device_idle_pct.hyst: the share of the traced window in which no
operation ran on the chip (1 − union of program executions / window),
over the hysteresis placer's ticks."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share()
