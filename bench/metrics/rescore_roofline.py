"""rescore_roofline: share of the HBM roofline reached by the sparse
greedy's re-score (Alg. 3 lines 15–19), the same work however it is
compiled.

Least bytes one re-score moves, from shapes: the [k, U] pair column ids
and QoS read (8·k·U), each user's edge id, pick QoS and satisfied flag in
and out (10·U), the [E, P + 1] float32 grid the pairs scatter into
written (4·E·(P + 1)) and the [E, P] benefits read and written (8·E·P).
Calls are the program's ``placement.greedy_rescores`` counter (iterations
that placed and so ran the re-score); time is the device time under the
``greedy.rescore`` scope (``bench/greedy_phases.py``).
"""
from bench import greedy_phases


def bytes_per_call(users, pairs, edges, impls):
    return 8 * pairs * users + 10 * users + 4 * edges * (impls + 1) \
        + 8 * edges * impls


def read(run):
    if run.trace is None:
        return None
    calls = greedy_phases.counter("placement.greedy_rescores")
    ns = greedy_phases.phase_ns(run, "greedy.rescore")
    if not calls or not ns:
        return None
    f = run.facts
    work = calls * bytes_per_call(f["users"], f["max_impls"], f["edges"],
                                  f["impls"])
    bound_s = work / run.peaks["hbm_bytes_per_s"]
    return 100.0 * bound_s / (ns / 1e9)
