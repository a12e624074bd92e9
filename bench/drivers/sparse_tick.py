"""Driver: control ticks of the sparse placement, back to back.

Each step is one tick as an operator's control loop runs it: a fresh
population of users arrives as host arrays, ``repro.workloads
.evaluate_sparse([inst], use_kernel=True)`` builds the candidate QoS and
runs the sparse EGP greedy and σ on the device, and the placement ``x``
and σ come back to the host.

The fleet and catalog are the deployment, drawn once from the
configuration's ``catalog_seed``. The populations are a pool drawn in
set-up from the mix's ``population_seed``, as many as the window holds
ticks; ``--seed`` sets the order in which the window serves them, so that
every run does the same work (the work of a tick depends on its users).
Set-up also runs one tick on a population of its own, which compiles (or
loads) every program the window runs.

After the window, a sample of its ticks drawn from the seed is compared
with the float64 reference (``bench/reference.py``):

* ``x_edges_differ`` — edges whose placement differs from the reference's,
  among the edges whose float64 greedy met no near-tie (two fitting
  implementations within ``TIE_MARGIN`` of each other's benefit), whose
  order float32 may legitimately swap;
* ``sigma_rel_gap`` — |σ − σ_ref| / σ_ref: the candidate QoS, the
  placement and σ together;
* ``storage_overflow`` — edges whose placement exceeds their storage.

Each is the largest over the checked ticks.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import reference as ref
from bench import traffic_gen


def compare(fleet: ref.Fleet, users: ref.Users, x: np.ndarray,
            sigma: float) -> Dict[str, float]:
    """The numbers of one tick's answer ``(x, σ)`` against the reference
    on the same users."""
    margins = np.empty(fleet.E)
    x_ref, sigma_ref = ref.place("egp", fleet, users, margins=margins)
    decided = margins >= ref.TIE_MARGIN
    return {
        "x_edges_differ": float(((x != x_ref).any(axis=1) & decided).sum()),
        "sigma_rel_gap": abs(sigma - sigma_ref) / abs(sigma_ref),
        "storage_overflow": float(ref.storage_overflow(fleet, x)),
    }


def worst(per_answer: List[Dict[str, float]]) -> Dict[str, float]:
    """Each number's largest reading over the answers."""
    return {k: max(n[k] for n in per_answer) for k in per_answer[0]}


class Driver:
    unit = "tick"

    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed = cell, int(seed)
        dep = cell.config["deployment"]
        self.deployment, self.traffic = dep, cell.traffic
        self.fleet = ref.draw_fleet(np.random.default_rng(dep["catalog_seed"]),
                                    dep)
        n = int(cell.traffic["populations"])
        # populations 0 .. n-1 feed the window; population n warms up
        self.populations = traffic_gen.populations(
            self.fleet, dep, cell.traffic, cell.traffic["population_seed"],
            0, n + 1)
        self.order = traffic_gen.stream(self.seed, 1).permutation(n)
        self.device = devices[0]
        self.answers: List[Tuple[int, float, np.ndarray]] = []
        self.n_steps = 0

    # -- the system under test ----------------------------------------------
    def instance(self, users: ref.Users):
        """The program's instance for one population (host arrays)."""
        from repro.core.instance import PIESInstance

        f = self.fleet
        return PIESInstance(
            K=f.K, W=f.W, R=f.R, sm_service=f.sm_service, sm_acc=f.sm_acc,
            sm_k=f.sm_k, sm_w=f.sm_w, sm_r=f.sm_r, u_edge=users.edge,
            u_service=users.service, u_alpha=users.alpha,
            u_delta=users.delta, delta_max=f.delta_max)

    def tick(self, users: ref.Users) -> Tuple[float, np.ndarray]:
        """One control tick: host arrays in, ``(σ, x)`` on the host out."""
        import jax

        from repro.workloads import evaluate_sparse

        with jax.default_device(self.device):
            values, xs = evaluate_sparse([self.instance(users)],
                                         use_kernel=True)
            return float(values[0]), np.asarray(xs[0])

    def warm(self) -> None:
        self.tick(self.populations[-1])

    def step(self) -> int:
        i = int(self.order[self.n_steps % len(self.order)])
        sigma, x = self.tick(self.populations[i])
        self.answers.append((i, sigma, x))
        self.n_steps += 1
        return 1

    def facts(self) -> Dict:
        f = self.fleet
        return {"users": int(self.deployment["users"]["per_tick"]),
                "edges": f.E, "impls": f.P,
                "max_impls": int(ref.impl_table(f).shape[1]),
                "ticks": self.n_steps}

    # -- the comparison ------------------------------------------------------
    def sample(self) -> List[int]:
        """Positions of the checked answers, drawn from the seed."""
        k = min(int(self.traffic["checked_ticks"]), len(self.answers))
        rng = traffic_gen.stream(self.seed, 2)
        return sorted(rng.choice(len(self.answers), size=k, replace=False))

    def check(self) -> Tuple[Dict[str, float], int]:
        """``(numbers, failed answers)`` over the sampled ticks."""
        per, failed = [], 0
        for j in self.sample():
            i, sigma, x = self.answers[j]
            n = compare(self.fleet, self.populations[i], x, sigma)
            failed += not all(n[k] <= float(v["limit"])
                              for k, v in self.cell.limits.items())
            per.append(n)
        return worst(per), failed
