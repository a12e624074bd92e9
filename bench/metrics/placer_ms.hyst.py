"""placer_ms.hyst: device time per tick of the hysteresis placer's
program: the biased sparse greedy, σ and the tick's loads and evictions.

``repro.workloads.batched.placer_program`` jits a function named
``place``, so the trace names its program ``jit_place``. That name is the
whole mapping: a program without it leaves this metric silent.
"""

PROGRAM = r"jit_place\b"


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    ev = t.programs(t.devices[0], PROGRAM)
    if not len(ev):
        return None
    return int(ev.dur.sum()) / 1e6 / run.steps
