"""greedy_iters.tick: iterations of the sparse greedy's loop per tick,
from the program's ``placement.greedy_iters`` counter (kept while the
trace runs). Every edge advances by one pick an iteration, so the count
is the slowest edge's number of picks and considerations."""
from bench import greedy_phases


def read(run):
    if run.trace is None:
        return None
    n = greedy_phases.counter("placement.greedy_iters")
    return None if n is None else n / run.steps
