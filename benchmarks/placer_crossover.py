"""Where :class:`repro.core.dynamic.DynamicPlacer` should leave the host.

Times one warm hysteresis step on each of its two paths, the float64 host
loop (``_egp_with_bias`` over a dense QoS matrix, the QoS included) and
the device placer (``place_on_device``), over §VI-B instances of growing
U·P, and prints one JSON line per size and last the crossover: the least
U·P from which the device step is the faster at every larger size
measured. Each step starts from the resident set of a previous step, as
in a horizon, and each time is the least of ``--repeats`` steps, after a
first step that compiles the device path's programs.

    python -m benchmarks.placer_crossover [--repeats 3]

Run it on the host that serves (the device path's time is the
accelerator's); ``repro.core.dynamic.DEVICE_MIN_CELLS`` is where
``DynamicPlacer`` switches.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

#: (users, edges, services): the served scenarios' shape first, then the
#: paper's Fig. 4 fleet (10 edges) from 10 to 10⁴ users
SIZES = [(96, 6, 24)] + [(u, 10, 100) for u in
                         (10, 20, 50, 100, 200, 500, 1000, 2000, 5000,
                          10000)]


def _time_step(placer, inst, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        placer.step(inst)
        best = min(best, time.perf_counter() - t0)
    return best


def measure(n_users: int, n_edges: int, n_services: int,
            repeats: int) -> dict:
    from repro.core import dynamic
    from repro.core.instance import synthetic_instance

    inst = synthetic_instance(n_users, n_edges=n_edges,
                              n_services=n_services, seed=n_users)
    out = {"users": n_users, "edges": n_edges, "impls": inst.P,
           "cells": inst.U * inst.P}
    for path, threshold in (("host", float("inf")), ("device", 0)):
        dynamic.DEVICE_MIN_CELLS = threshold
        placer = dynamic.DynamicPlacer()
        placer.step(inst)                    # compiles; leaves residents
        out[f"{path}_ms"] = 1e3 * _time_step(placer, inst, repeats)
    return out


def crossover(rows) -> int:
    """The least U·P from which the device step is faster at every
    larger size (the largest measured U·P + 1 where it never is)."""
    rows = sorted(rows, key=lambda r: r["cells"])
    best = rows[-1]["cells"] + 1
    for r in reversed(rows):
        if r["device_ms"] >= r["host_ms"]:
            break
        best = r["cells"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)

    import jax

    from repro.core import dynamic

    saved = dynamic.DEVICE_MIN_CELLS
    rows = []
    try:
        for u, e, s in SIZES:
            rows.append(measure(u, e, s, args.repeats))
            print(json.dumps(rows[-1]), flush=True)
    finally:
        dynamic.DEVICE_MIN_CELLS = saved
    dev = jax.devices()[0]
    print(json.dumps({"crossover_cells": crossover(rows),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
