"""qos_candidates_roofline: share of the HBM roofline reached by the
candidate QoS kernel, the same work whichever implementation runs.

Work per call, from shapes: the four user vectors (α, δ, |U_e|/K_e,
|U_e|/W_e) and the four [U, M] pair attributes (A, k, w, valid) read in
float32, and the [U, M] QoS written: 16·U + 20·U·M bytes. Calls are the
``jit_qos_candidates`` programs in the window; time is the device time of
the ops under the ``qos_candidates_pallas`` or ``qos_candidates_ref``
scope. The bound is bytes / peak HBM bandwidth (its operations are a few
per byte, far under the compute peak).
"""

SCOPES = ("qos_candidates_pallas", "qos_candidates_ref")
PROGRAM = r"jit_qos_candidates\b"


def bytes_per_call(users, max_impls):
    return 16 * users + 20 * users * max_impls


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    dev = t.devices[0]
    ops = t.scope_ops(dev, SCOPES)
    calls = len(t.programs(dev, PROGRAM))
    if not len(ops) or not calls:
        return None
    work = calls * bytes_per_call(run.facts["users"], run.facts["max_impls"])
    bound_s = work / run.peaks["hbm_bytes_per_s"]
    return 100.0 * bound_s / (ops.dur.sum() / 1e9)
