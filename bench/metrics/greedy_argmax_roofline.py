"""greedy_argmax_roofline: share of the HBM roofline reached by the
per-edge masked argmax of the sparse greedy (Alg. 3 line 11), the same
work whichever implementation runs.

Work per call, from shapes: the [E, P] benefits and the [E, P] mask read
in float32: 8·E·P bytes (the [E] outputs are negligible). Calls are
counted in the trace: every call runs each op of the scope once, so the
most frequent op name under the ``greedy_argmax_pallas`` or
``greedy_argmax_ref`` scope counts them. Time is the device time of the
ops under that scope.
"""
from collections import Counter

SCOPES = ("greedy_argmax_pallas", "greedy_argmax_ref")


def bytes_per_call(edges, impls):
    return 8 * edges * impls


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    ops = t.scope_ops(t.devices[0], SCOPES)
    if not len(ops):
        return None
    calls = max(Counter(ops.name).values())
    work = calls * bytes_per_call(run.facts["edges"], run.facts["impls"])
    bound_s = work / run.peaks["hbm_bytes_per_s"]
    return 100.0 * bound_s / (ops.dur.sum() / 1e9)
