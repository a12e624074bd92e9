"""greedy_ms.tick: device time per tick of the sparse greedy's program.

``repro.workloads.batched.sparse_evaluator`` jits a function named
``run`` (``egp_place_sparse_jax`` then ``sigma_sparse_jnp``), so the
trace names its program ``jit_run``. That name is the whole mapping: a
program change that renames it leaves this metric silent.
"""

PROGRAM = r"jit_run\b"


def program_ns(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    ev = t.programs(t.devices[0], PROGRAM)
    return int(ev.dur.sum()) if len(ev) else None


def read(run):
    ns = program_ns(run)
    return None if ns is None else ns / 1e6 / run.steps
