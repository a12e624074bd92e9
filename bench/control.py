"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --mode program --seeds 1,2,3
    python3 bench/control.py --workload <cell> --mode control --seeds 1,2,3

``program`` drives the timed path as a run does (one process, set-up
once, ``--steps`` steps per seed) and prints, per seed, the numbers the
cell compares: the sound runs' readings, whose largest is a limit's lower
reading. ``control`` puts the control in the program's place: the plain
reference computed in bfloat16, one precision step below the float32 the
configurations state. Its smallest reading is a limit's upper one. The
benchmark's own runs never run this script.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import reference as ref  # noqa: E402


def sparse_tick_control(fleet):
    """``evaluate_sparse`` replaced by the bfloat16 reference."""

    def control(instances, **kw):
        inst = instances[0]
        users = ref.Users(edge=inst.u_edge, service=inst.u_service,
                          alpha=inst.u_alpha, delta=inst.u_delta)
        x, s = ref.place("egp", fleet, users, "bfloat16")
        return np.asarray([s]), [x]

    return control


def sweep_control(deployment):
    """``run_sweep`` replaced by the bfloat16 reference, item by item."""
    from repro.sweeps.spec import variant_key

    class Result:
        def __init__(self, values):
            self.values = values

    def control(spec, **kw):
        values = {}
        for ov in spec.override_grid:
            n_users = dict(ov)["n_users"]
            for algo in spec.algos:
                vals = np.empty((len(spec.seeds), 1))
                for i, seed in enumerate(spec.seeds):
                    fleet, users = ref.draw_trial(seed, n_users, deployment)
                    vals[i, 0] = ref.place(algo, fleet, users, "bfloat16")[1]
                values[(variant_key("synthetic", ov), algo)] = vals
        return Result(values)

    return control


def plant_control(cell, drv) -> None:
    """Put the control in the place of the cell's timed entry."""
    driver = cell.traffic["driver"]
    if driver == "sparse_tick":
        import repro.workloads as W

        W.evaluate_sparse = sparse_tick_control(drv.fleet)
    elif driver == "sweep":
        import repro.sweeps as S

        S.run_sweep = sweep_control(cell.config["deployment"])
    else:
        raise ValueError(f"no control for driver {driver!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=("program", "control"), required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one reading each")
    ap.add_argument("--steps", type=int, default=1)
    args = ap.parse_args(argv)

    cell = harness.load_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                             args.workload)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()[:cell.chips]
    warmed = False
    for seed in (int(s) for s in args.seeds.split(",")):
        drv = cell.driver().Driver(cell, seed, devices)
        if args.mode == "control":
            plant_control(cell, drv)
        elif not warmed:
            drv.warm()
            warmed = True
        t0 = time.perf_counter()
        for _ in range(args.steps):
            drv.step()
        numbers, failed = drv.check()
        print(json.dumps({"workload": cell.name, "mode": args.mode,
                          "seed": seed, "failed": failed,
                          "seconds": time.perf_counter() - t0,
                          "numbers": numbers,
                          "device": devices[0].platform}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
