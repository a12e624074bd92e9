"""Driver: passes of a Monte-Carlo grid through the sweeps engine.

Each step is one pass, one call of ``repro.sweeps.run_sweep(spec,
mesh=make_sweep_mesh())`` with no store: every user count of the mix ×
its trials × its algorithms, one tick each, on the ``shard_map`` path
across all the cell's chips. The program draws each trial's §VI-B
instance itself from the trial seed.

The trials are one pool, drawn from the mix's ``trial_seed``: every pass
of every run evaluates the same items, so every run does the same work.
``--seed`` sets the order in which a pass visits the grid's user counts.
Set-up runs one pass, which compiles (or loads) every program the window
calls and lets the engine make the extra timing run it makes for a chunk
layout new to the process.

After the window every answer is compared with the float64 reference,
which draws the same instances from the same trial seeds
(``bench/reference.py``):

* ``sigma_rel_gap`` — the largest |σ − σ_ref| / σ_ref over the answers;
* ``items_missing`` — answers of the window with no value.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

from bench import reference as ref
from bench import traffic_gen


class Driver:
    unit = "pass"

    def __init__(self, cell, seed: int, devices):
        from repro.launch.mesh import make_sweep_mesh

        self.cell, self.seed = cell, int(seed)
        self.deployment, self.traffic = cell.config["deployment"], cell.traffic
        users = [int(u) for u in self.traffic["users"]]
        order = traffic_gen.stream(self.seed, 1).permutation(len(users))
        self.users = [users[i] for i in order]
        self.trials = int(self.traffic["trials"])
        self.trial_seeds = traffic_gen.trial_seeds(self.traffic["trial_seed"],
                                                  self.trials)
        self.algos = tuple(self.traffic["algos"])
        self.n_devices = len(devices)
        # all the cell's chips (make_sweep_mesh() on a host of exactly those)
        self.mesh = make_sweep_mesh(self.n_devices)
        # per pass, {(n_users, algo): σ per trial}
        self.passes: List[Dict] = []

    # -- the system under test ----------------------------------------------
    def overrides(self, n_users: int) -> Dict:
        d = self.deployment
        return {"n_users": n_users, "n_edges": d["edges"]["count"],
                "n_services": d["catalog"]["services"],
                "max_impls": d["catalog"]["max_impls"],
                "delta_max": d["users"]["delta_max"],
                "alpha_scale": d["users"]["alpha_scale"],
                "delta_scale": d["users"]["delta_scale"]}

    def spec(self, seeds):
        from repro.sweeps import SweepSpec

        return SweepSpec(scenarios=("synthetic",), seeds=tuple(seeds),
                         n_ticks=1, algos=self.algos,
                         override_grid=tuple(self.overrides(u)
                                             for u in self.users))

    def run_pass(self) -> Dict:
        from repro.sweeps import run_sweep
        from repro.sweeps.spec import variant_key

        spec = self.spec(self.trial_seeds)
        res = run_sweep(spec, mesh=self.mesh)
        values = {}
        for u, ov in zip(self.users, spec.override_grid):
            for algo in self.algos:
                values[(u, algo)] = res.values[(variant_key("synthetic", ov),
                                                algo)][:, 0]
        return values

    def warm(self) -> None:
        self.run_pass()

    def step(self) -> int:
        self.passes.append(self.run_pass())
        return len(self.users) * self.trials * len(self.algos)

    def facts(self) -> Dict:
        return {"passes": len(self.passes),
                "items_per_pass": len(self.users) * self.trials
                * len(self.algos),
                "devices": self.n_devices}

    # -- the comparison ------------------------------------------------------
    def check(self) -> Tuple[Dict[str, float], int]:
        """``(numbers, failed answers)`` over every answer of the window;
        the reference runs once per item of the pool."""
        limit = float(self.cell.limits["sigma_rel_gap"]["limit"])
        worst, failed, missing = 0.0, 0, 0
        for (u, algo) in self.passes[0]:
            for t, trial_seed in enumerate(self.trial_seeds):
                fleet, users = ref.draw_trial(trial_seed, u, self.deployment)
                s_ref = ref.place(algo, fleet, users)[1]
                for values in self.passes:
                    value = float(values[(u, algo)][t])
                    gap = abs(value - s_ref) / abs(s_ref)
                    missing += not math.isfinite(value)
                    failed += not gap <= limit
                    worst = max(worst, gap) if math.isfinite(gap) \
                        else math.inf
        return {"sigma_rel_gap": worst, "items_missing": float(missing)}, \
            failed
