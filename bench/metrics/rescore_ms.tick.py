"""rescore_ms.tick: device time per tick of the sparse greedy's
``greedy.rescore`` phase, lines 15–19 of Algorithm 3, the ``lax.cond``
that runs when some edge placed: the pair scatter into the [E, P + 1]
grid (a custom fusion and the sort that feeds it), the sibling update and
the satisfied users. Ops the compiler made without a scope take theirs
from the compiled program (``bench/greedy_phases.py``)."""
from bench import greedy_phases


def read(run):
    ns = greedy_phases.phase_ns(run, "greedy.rescore")
    return None if ns is None else ns / 1e6 / run.steps
