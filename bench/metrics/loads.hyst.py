"""loads.hyst: implementations newly loaded per tick, from the program's
``placement.loads`` counter (kept while the trace runs): what the
hysteresis saves, and what each tick pays its switching cost on."""
from bench import greedy_phases


def read(run):
    if run.trace is None:
        return None
    n = greedy_phases.counter("placement.loads")
    return None if n is None else n / run.steps
