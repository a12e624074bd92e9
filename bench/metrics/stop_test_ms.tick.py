"""stop_test_ms.tick: device time per tick of the sparse greedy's
``greedy.stop_test`` phase: line 17's ``considered`` update, the per-edge
count of unsatisfied users and the stop test of line 20. Ops the compiler
made without a scope take theirs from the compiled program
(``bench/greedy_phases.py``)."""
from bench import greedy_phases


def read(run):
    ns = greedy_phases.phase_ns(run, "greedy.stop_test")
    return None if ns is None else ns / 1e6 / run.steps
