"""Readings for the limits of ``azure-fleet-day``, as ``bench/control.py``
gives them for the cells it knows:

    python3 bench/control_hysteresis.py --workload azure-fleet-day --mode program --seeds 1,2,3
    python3 bench/control_hysteresis.py --workload azure-fleet-day --mode control --seeds 1,2,3 --steps 2

``control`` puts the plain reference computed in bfloat16 in the timed
path's place: a placer that runs ``bench/reference_hysteresis.py`` in
bfloat16 over its own resident set. The options and the output are
``bench/control.py``'s.
"""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control  # noqa: E402
from bench import reference as ref  # noqa: E402
from bench import reference_hysteresis as ref_h  # noqa: E402


class ControlPlacer:
    """``DynamicPlacer``'s surface over the bfloat16 reference."""

    def __init__(self, knobs, n_services: int):
        self.stickiness = float(knobs["stickiness"])
        self.switching_cost = float(knobs["switching_cost"])
        self.n_services = n_services
        self.resident = None
        self.new_loads = self.evicted = None

    def step(self, inst, Q=None):
        fleet = ref.Fleet(K=inst.K, W=inst.W, R=inst.R,
                          sm_service=inst.sm_service, sm_acc=inst.sm_acc,
                          sm_k=inst.sm_k, sm_w=inst.sm_w, sm_r=inst.sm_r,
                          n_services=self.n_services,
                          delta_max=float(inst.delta_max))
        users = ref.Users(edge=inst.u_edge, service=inst.u_service,
                          alpha=inst.u_alpha, delta=inst.u_delta)
        if self.resident is None:
            self.resident = np.zeros((fleet.E, fleet.P), bool)
        r = ref_h.place(fleet, users, self.resident, self.stickiness,
                        self.switching_cost, "bfloat16")
        self.new_loads, self.evicted = r["new_loads"], self.resident & ~r["x"]
        self.resident = r["x"]
        return r["x"], r["value"], r["loads"]


def plant_control(cell, drv) -> None:
    """Put the control in the place of the cell's timed entry."""
    driver = cell.traffic["driver"]
    if driver != "hysteresis_tick":
        raise ValueError(f"no control here for driver {driver!r}: "
                         f"bench/control.py has the others")
    drv.placer = ControlPlacer(cell.config["placer"], drv.fleet.n_services)


if __name__ == "__main__":
    control.plant_control = plant_control
    sys.exit(control.main())
