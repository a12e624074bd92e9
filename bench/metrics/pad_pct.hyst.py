"""pad_pct.hyst: the share of the placer's user slots that are padding,
from the program's ``placement.pad_users`` and ``placement.user_slots``
counters (kept while the trace runs): each tick's users are padded to a
size class so that no tick compiles anew."""
from bench import greedy_phases


def read(run):
    if run.trace is None:
        return None
    pad = greedy_phases.counter("placement.pad_users")
    slots = greedy_phases.counter("placement.user_slots")
    if pad is None or not slots:
        return None
    return 100.0 * pad / slots
