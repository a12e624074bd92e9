"""pick_ms.tick: device time per tick of the sparse greedy's
``greedy.pick`` phase, lines 11–14 of Algorithm 3: the candidate mask,
the per-edge argmax, the fit test and the placement, and the per-user
gathers of the pick. Ops the compiler made without a scope take theirs
from the compiled program (``bench/greedy_phases.py``)."""
from bench import greedy_phases


def read(run):
    ns = greedy_phases.phase_ns(run, "greedy.pick")
    return None if ns is None else ns / 1e6 / run.steps
