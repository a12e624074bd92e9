"""Driver: control ticks of the hysteresis placer through a day of traffic.

Each step is one control tick as an operator's control loop runs it: the
tick's population arrives as host arrays, ``repro.core.dynamic
.DynamicPlacer.step`` re-places the fleet from the resident set the
previous tick left (on the device at this size: the users padded to a
size class, the candidate QoS, the biased sparse EGP greedy and σ), and
the placement, its value and its loads come back to the host, while the
resident set stays on the device for the next tick.

The fleet and catalog are the deployment, drawn once from the
configuration's ``catalog_seed``. The mix gives a cycle of ticks: the
users of each (a day's volume), uniform over the edges, their services
Zipf-distributed with a ranking that rotates by ``service_drift``
services a tick, and a failure: on the cycle's ``failure.ticks`` a fixed
set of ``failure.edges`` edges has no storage and its users move to the
next live edge on the ring. The cycle's populations are drawn in set-up
from the mix's ``population_seed``; set-up then runs one whole cycle,
which compiles (or loads) every program of every size class and brings
the resident set to its steady state. The window serves the cycle in
order from its first tick, wrapping, the resident set carried across the
wrap. ``--seed`` picks only which ticks are checked, so every run does
the same work.

After the window, ``checked_ticks`` ticks drawn from the seed, and
always the window's first failure tick, are compared with the float64
reference (``bench/reference_hysteresis.py``), each given the resident
set the program's own previous tick left:

* ``x_edges_differ`` — edges whose placement differs from the
  reference's, among the edges whose float64 greedy met no near-tie of
  biased benefits (``reference.TIE_MARGIN``);
* ``loads_differ`` — among the same edges, those whose newly loaded
  implementations differ from the reference's;
* ``sigma_rel_gap`` — |σ − σ_ref| / σ_ref, with σ = value + switching
  cost · loads;
* ``storage_overflow`` — edges whose placement exceeds their storage: a
  failed edge, with none, holds nothing.

Each is the largest over the checked ticks.
"""
import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from bench import reference as ref
from bench import reference_hysteresis as ref_h
from bench import traffic_gen


def ring_successor(failed: np.ndarray) -> np.ndarray:
    """``[E]``: each edge itself where it is live, else the next live edge
    on the ring."""
    E = failed.shape[0]
    live = np.nonzero(~failed)[0]
    nxt = live[np.searchsorted(live, np.arange(E)) % live.size]
    return np.where(failed, nxt, np.arange(E))


def cycle(fleet: ref.Fleet, deployment: Dict, traffic: Dict, seed: int
          ) -> Tuple[List[ref.Users], List[ref.Fleet], np.ndarray]:
    """The mix's cycle: each tick's users and fleet (storage 0 at the
    failed edges on a failure tick), and the failed edges' mask."""
    E, S = fleet.E, fleet.n_services
    fail = traffic["failure"]
    failed = np.zeros(E, bool)
    failed[traffic_gen.stream(seed, 1).choice(E, size=int(fail["edges"]),
                                              replace=False)] = True
    moved = ring_successor(failed)
    down = dataclasses.replace(fleet, R=np.where(failed, 0.0, fleet.R))
    drift = int(traffic["service_drift"])
    users, fleets = [], []
    for t, n in enumerate(traffic["users"]):
        u = ref.draw_users(traffic_gen.stream(seed, 0, t), fleet, int(n),
                           deployment, traffic)
        u.service = (u.service + drift * t) % S
        if t in fail["ticks"]:
            u.edge = moved[u.edge]
            fleets.append(down)
        else:
            fleets.append(fleet)
        users.append(u)
    return users, fleets, failed


@dataclasses.dataclass
class Answer:
    tick: int                # position in the cycle
    resident: np.ndarray     # [E, P] the resident set the tick started from
    x: np.ndarray
    new_loads: np.ndarray
    value: float
    loads: int


class Driver:
    unit = "tick"

    def __init__(self, cell, seed: int, devices):
        # the program's device placer; a program without it fails here
        from repro.workloads.batched import place_on_device  # noqa: F401
        from repro.core.dynamic import DynamicPlacer

        self.cell, self.seed = cell, int(seed)
        dep = cell.config["deployment"]
        self.deployment, self.traffic = dep, cell.traffic
        self.knobs = cell.config["placer"]
        self.fleet = ref.draw_fleet(np.random.default_rng(dep["catalog_seed"]),
                                    dep)
        self.users, self.fleets, self.failed = cycle(
            self.fleet, dep, self.traffic, self.traffic["population_seed"])
        self.instances = [self.instance(u, f)
                          for u, f in zip(self.users, self.fleets)]
        self.placer = DynamicPlacer(
            switching_cost=float(self.knobs["switching_cost"]),
            stickiness=float(self.knobs["stickiness"]))
        self.device = devices[0]
        self.resident = np.zeros((self.fleet.E, self.fleet.P), bool)
        self.answers: List[Answer] = []
        self.n_steps = 0

    # -- the system under test ----------------------------------------------
    @staticmethod
    def instance(users: ref.Users, f: ref.Fleet):
        """The program's instance for one tick (host arrays)."""
        from repro.core.instance import PIESInstance

        return PIESInstance(
            K=f.K, W=f.W, R=f.R, sm_service=f.sm_service, sm_acc=f.sm_acc,
            sm_k=f.sm_k, sm_w=f.sm_w, sm_r=f.sm_r, u_edge=users.edge,
            u_service=users.service, u_alpha=users.alpha,
            u_delta=users.delta, delta_max=f.delta_max)

    def tick(self, t: int) -> Answer:
        """One control tick of the cycle's tick ``t``."""
        import jax

        with jax.default_device(self.device):
            x, value, loads = self.placer.step(self.instances[t])
        answer = Answer(tick=t, resident=self.resident, x=x,
                        new_loads=self.placer.new_loads, value=value,
                        loads=loads)
        self.resident = x
        return answer

    def warm(self) -> None:
        for t in range(len(self.instances)):
            self.tick(t)

    def step(self) -> int:
        self.answers.append(self.tick(self.n_steps % len(self.instances)))
        self.n_steps += 1
        return 1

    def facts(self) -> Dict:
        f, n = self.fleet, np.asarray(self.traffic["users"])
        return {"users_peak": int(n.max()), "users_mean": float(n.mean()),
                "edges": f.E, "impls": f.P,
                "max_impls": int(ref.impl_table(f).shape[1]),
                "failed_edges": int(self.failed.sum()),
                "cycle": int(n.size), "ticks": self.n_steps}

    # -- the comparison ------------------------------------------------------
    def compare(self, a: Answer, margins=None) -> Dict[str, float]:
        """The numbers of one tick's answer against the reference given
        the same resident set."""
        fleet = self.fleets[a.tick]
        margins = np.empty(fleet.E) if margins is None else margins
        r = ref_h.place(fleet, self.users[a.tick], a.resident,
                        float(self.knobs["stickiness"]),
                        float(self.knobs["switching_cost"]),
                        margins=margins)
        decided = margins >= ref.TIE_MARGIN
        sigma = a.value + float(self.knobs["switching_cost"]) * a.loads
        return {
            "x_edges_differ": float(((a.x != r["x"]).any(axis=1)
                                     & decided).sum()),
            "loads_differ": float(((a.new_loads != r["new_loads"]).any(axis=1)
                                   & decided).sum()),
            "sigma_rel_gap": abs(sigma - r["sigma"]) / abs(r["sigma"]),
            "storage_overflow": float(ref.storage_overflow(fleet, a.x)),
        }

    def sample(self) -> List[int]:
        """Positions of the checked answers: ``checked_ticks`` drawn from
        the seed, and the window's first failure tick."""
        n = len(self.answers)
        first = [j for j, a in enumerate(self.answers)
                 if a.tick in self.traffic["failure"]["ticks"]][:1]
        rest = [j for j in range(n) if j not in first]
        k = min(int(self.traffic["checked_ticks"]), len(rest))
        rng = traffic_gen.stream(self.seed, 2)
        return sorted(first + [rest[i] for i in
                               rng.choice(len(rest), size=k, replace=False)])

    def check(self) -> Tuple[Dict[str, float], int]:
        """``(numbers, failed answers)`` over the sampled ticks."""
        per, failed = [], 0
        for j in self.sample():
            n = self.compare(self.answers[j])
            failed += not all(n[k] <= float(v["limit"])
                              for k, v in self.cell.limits.items())
            per.append(n)
        return {k: max(n[k] for n in per) for k in per[0]}, failed
