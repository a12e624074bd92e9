"""The plain reference: §VI-B draws, QoS (Eqs. 1-6), EGP (Alg. 3), AGP
(Alg. 2) and σ (Eq. 9), in float64 NumPy.

It imports nothing of the program under test and takes nothing the
program made: it draws its own instances from the same seeds and
recomputes every answer. The arithmetic is the paper's; only the loop
structure is arranged for speed:

* EGP's picks at an edge depend only on that edge's users, and a pick's
  re-score touches only the users who request the picked service. So the
  greedy runs edge by edge over ``(edge, service)`` groups of users.
* An implementation that does not fit the remaining storage never fits
  later (storage only shrinks), and considering it changes nothing but
  the "considered" set. So each step takes the best unconsidered
  implementation among those that fit. The placement is the one
  Algorithm 3 makes, pick for pick among placed implementations.

``rounding`` selects the precision: ``"float64"`` is the reference;
``"bfloat16"`` rounds every stored value (QoS, benefits, re-scores, σ per
user) to bfloat16 and is the control that a sound comparison must fail.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import numpy as np

#: slack of the storage test ``r <= remaining + tol`` (the paper's
#: feasibility test, as both the host and device placements apply it)
FEASIBILITY_TOL = 1e-6
#: a user counts as fully satisfied at QoS >= 1 - SATISFIED_TOL
SATISFIED_TOL = 1e-9
#: two benefits closer than this are a near-tie: a float32 sum of the
#: ~10 QoS values behind one benefit is off by under 1e-6, so the order of
#: a near-tie may legitimately differ from float64's, and none other may
TIE_MARGIN = 1e-5


def rounder(rounding: str) -> Callable[[np.ndarray], np.ndarray]:
    """The function that rounds a float64 array to the stated precision."""
    if rounding == "float64":
        return lambda a: np.asarray(a, np.float64)
    if rounding == "bfloat16":
        import ml_dtypes

        return lambda a: np.asarray(a, np.float64).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown rounding {rounding!r}")


# ===========================================================================
# §VI-B draws
# ===========================================================================

@dataclasses.dataclass
class Fleet:
    """Edges and the implementation catalog: the deployment."""

    K: np.ndarray           # [E] communication capacity
    W: np.ndarray           # [E] computation capacity
    R: np.ndarray           # [E] storage capacity
    sm_service: np.ndarray  # [P] service of each implementation
    sm_acc: np.ndarray      # [P] accuracy A
    sm_k: np.ndarray        # [P] communication cost
    sm_w: np.ndarray        # [P] computation cost
    sm_r: np.ndarray        # [P] storage cost
    n_services: int
    delta_max: float

    @property
    def E(self) -> int:
        return int(self.K.shape[0])

    @property
    def P(self) -> int:
        return int(self.sm_service.shape[0])


@dataclasses.dataclass
class Users:
    """One population of requests."""

    edge: np.ndarray     # [U] covering edge
    service: np.ndarray  # [U] requested service
    alpha: np.ndarray    # [U] accuracy threshold
    delta: np.ndarray    # [U] delay threshold


def draw_fleet(rng: np.random.Generator, deployment: Dict) -> Fleet:
    """Edges ``K, W ~ U{lo..hi}``, ``R ~ U{lo..hi}``; services with
    ``U{1..max_impls}`` implementations, ``k, w, r ~ U{lo..hi}``,
    ``A ~ clip(N(mean, sd), 0, 1)``: the §VI-B draws, in their order."""
    e, c = deployment["edges"], deployment["catalog"]
    n_edges = int(e["count"])
    K = rng.integers(e["K"][0], e["K"][1] + 1, size=n_edges).astype(np.float64)
    W = rng.integers(e["W"][0], e["W"][1] + 1, size=n_edges).astype(np.float64)
    R = rng.integers(e["R"][0], e["R"][1] + 1, size=n_edges).astype(np.float64)
    n_services = int(c["services"])
    impls = rng.integers(1, int(c["max_impls"]) + 1, size=n_services)
    sm_service = np.repeat(np.arange(n_services), impls)
    P = sm_service.shape[0]
    sm_k = rng.integers(c["k"][0], c["k"][1] + 1, size=P).astype(np.float64)
    sm_w = rng.integers(c["w"][0], c["w"][1] + 1, size=P).astype(np.float64)
    sm_r = rng.integers(c["r"][0], c["r"][1] + 1, size=P).astype(np.float64)
    sm_acc = np.clip(rng.normal(c["acc_mean"], c["acc_sd"], size=P), 0.0, 1.0)
    return Fleet(K=K, W=W, R=R, sm_service=sm_service, sm_acc=sm_acc,
                 sm_k=sm_k, sm_w=sm_w, sm_r=sm_r, n_services=n_services,
                 delta_max=float(deployment["users"]["delta_max"]))


def popularity(rng: np.random.Generator, n: int, size: int,
               spec: Dict) -> np.ndarray:
    """``size`` draws from ``n`` categories under a popularity ``spec``:
    ``uniform``; ``zipf`` (weight of rank i ∝ 1 / i^s); ``hotspot`` (a
    ``hot_fraction`` of the categories takes ``hot_share`` of the draws)."""
    kind = spec.get("kind", "uniform")
    if kind == "uniform":
        return rng.integers(0, n, size=size)
    if kind == "zipf":
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** float(spec["s"])
    elif kind == "hotspot":
        n_hot = max(1, int(round(n * float(spec["hot_fraction"]))))
        share = float(spec["hot_share"])
        w = np.full(n, (1.0 - share) / max(n - n_hot, 1))
        w[:n_hot] = share / n_hot
    else:
        raise ValueError(f"unknown popularity kind {kind!r}")
    return rng.choice(n, size=size, p=w / w.sum())


def draw_users(rng: np.random.Generator, fleet: Fleet, n_users: int,
               deployment: Dict, traffic: Optional[Dict] = None) -> Users:
    """User draws in the §VI-B order: edge, service, ``α = 1 − clip(Exp(
    alpha_scale), 0, 1)``, ``δ = clip(Exp(delta_scale), 0, δ_max)``."""
    traffic = traffic or {}
    u = deployment["users"]
    edge = popularity(rng, fleet.E, n_users,
                      traffic.get("edge_popularity", {}))
    service = popularity(rng, fleet.n_services, n_users,
                         traffic.get("service_popularity", {}))
    alpha = 1.0 - np.clip(rng.exponential(u["alpha_scale"], size=n_users),
                          0.0, 1.0)
    delta = np.clip(rng.exponential(u["delta_scale"], size=n_users), 0.0,
                    fleet.delta_max)
    return Users(edge=edge, service=service, alpha=alpha, delta=delta)


def draw_trial(seed: int, n_users: int, deployment: Dict):
    """A whole §VI-B instance from one seed: fleet, catalog, then users,
    all from one generator, as the paper's per-trial draw does."""
    rng = np.random.default_rng(seed)
    fleet = draw_fleet(rng, deployment)
    return fleet, draw_users(rng, fleet, n_users, deployment)


# ===========================================================================
# QoS over (user, implementation of its service) pairs
# ===========================================================================

def impl_table(fleet: Fleet) -> np.ndarray:
    """``[S, M]`` implementation indices per service, −1 padded."""
    counts = np.bincount(fleet.sm_service, minlength=fleet.n_services)
    first = np.concatenate([[0], np.cumsum(counts)[:-1]])
    M = int(counts.max())
    j = np.arange(M)
    return np.where(j[None, :] < counts[:, None], first[:, None] + j[None, :],
                    -1)


def pair_qos(fleet: Fleet, users: Users, rnd=rounder("float64")):
    """``(cand [U, M], Q [U, M])``: the implementations of each user's
    service and their QoS (Eq. 1), 0 where ``cand`` is −1."""
    table = impl_table(fleet)
    cand = table[users.service]
    valid = cand >= 0
    p = np.where(valid, cand, 0)
    counts = np.bincount(users.edge, minlength=fleet.E).astype(np.float64)
    share_k = counts[users.edge] / fleet.K[users.edge]   # Eq. 5
    share_w = counts[users.edge] / fleet.W[users.edge]   # Eq. 6
    diff = users.alpha[:, None] - fleet.sm_acc[p]
    a_hat = np.where(diff <= 0.0, 1.0, np.maximum(0.0, 1.0 - diff))  # Eq. 2
    D = fleet.sm_k[p] * share_k[:, None] + fleet.sm_w[p] * share_w[:, None]
    over = D - users.delta[:, None]
    d_hat = np.where(over <= 0.0, 1.0,
                     np.maximum(0.0, 1.0 - over / fleet.delta_max))  # Eq. 3
    Q = rnd(np.where(valid, 0.5 * (a_hat + d_hat), 0.0))             # Eq. 1
    return cand, Q


# ===========================================================================
# σ (Eq. 9 under OMS, Alg. 1)
# ===========================================================================

def sigma(fleet: Fleet, users: Users, x: np.ndarray,
          cand: np.ndarray, Q: np.ndarray, rnd=rounder("float64")) -> float:
    """Each user is served by its best placed implementation at its edge."""
    placed = (cand >= 0) & x[users.edge[:, None], np.where(cand >= 0, cand, 0)]
    return float(rnd(np.where(placed, Q, 0.0).max(axis=1)).sum())


def storage_overflow(fleet: Fleet, x: np.ndarray) -> int:
    """Edges whose placed implementations exceed their storage."""
    used = (x * fleet.sm_r[None, :]).sum(axis=1)
    return int((used > fleet.R + FEASIBILITY_TOL).sum())


# ===========================================================================
# EGP (Algorithm 3)
# ===========================================================================

def egp(fleet: Fleet, users: Users, cand: np.ndarray, Q: np.ndarray,
        rnd=rounder("float64"), margins: Optional[np.ndarray] = None
        ) -> np.ndarray:
    """Algorithm 3 at every edge; returns ``x [E, P]`` bool. With
    ``margins`` (``[E]``), each edge's smallest gap between the benefit
    of a placed implementation and the next best that also fitted."""
    E, P, S = fleet.E, fleet.P, fleet.n_services
    table = impl_table(fleet)
    n_impl = (table >= 0).sum(axis=1)
    first = table[:, 0]
    # users ordered by (edge, service): each group is one slice
    key = users.edge.astype(np.int64) * S + users.service
    order = np.argsort(key, kind="stable")
    Qs = Q[order]
    bounds = np.concatenate([[0], np.cumsum(np.bincount(key, minlength=E * S))])
    # lines 3-6: v[(s, m)] = Σ_{u ∈ U_e} Q(u, s_u, m)
    valid = cand >= 0
    flat = (users.edge[:, None] * P + np.where(valid, cand, 0))[valid]
    v0 = rnd(np.bincount(flat, weights=Q[valid], minlength=E * P)
             ).reshape(E, P)
    rel = (np.bincount(flat, minlength=E * P) > 0).reshape(E, P)
    x = np.zeros((E, P), dtype=bool)
    if margins is None:
        margins = np.empty(E)
    for e in range(E):
        margins[e] = _egp_edge(e, x[e], v0[e].copy(), rel[e], fleet, first,
                               n_impl, Qs, bounds, S, rnd)
    return x


def _egp_edge(e, x_e, v, relevant, fleet, first, n_impl, Qs, bounds, S,
              rnd) -> float:
    sat: Dict[int, np.ndarray] = {}          # B, per service group at e
    n_users = int(bounds[(e + 1) * S] - bounds[e * S])
    n_sat = 0
    open_ = relevant.copy()                  # relevant and not considered
    remaining = float(fleet.R[e])            # R̂
    margin = np.inf
    while n_sat < n_users:
        fits = open_ & (fleet.sm_r <= remaining + FEASIBILITY_TOL)
        if not fits.any():
            break
        scores = np.where(fits, v, -np.inf)
        p = int(np.argmax(scores))                         # line 11
        scores[p] = -np.inf
        margin = min(margin, v[p] - scores.max())
        x_e[p] = True                                      # lines 12-14
        remaining -= float(fleet.sm_r[p])
        s = int(fleet.sm_service[p])
        lo, hi = bounds[e * S + s], bounds[e * S + s + 1]
        m = int(n_impl[s])
        Qg = Qs[lo:hi, :m]                   # the group's users × impls of s
        done = sat.setdefault(s, np.zeros(hi - lo, dtype=bool))
        j = p - int(first[s])
        # lines 15-16: re-score unconsidered siblings over unsatisfied users
        diff = rnd((Qg[~done] - Qg[~done, j:j + 1]).sum(axis=0))
        sib = open_[first[s]:first[s] + m].copy()
        sib[j] = False
        v[first[s]:first[s] + m] = np.where(sib, diff,
                                            v[first[s]:first[s] + m])
        # lines 18-19: users fully satisfied by the placed implementation
        newly = ~done & (Qg[:, j] >= 1.0 - SATISFIED_TOL)
        done |= newly
        n_sat += int(newly.sum())
        open_[p] = False                                    # line 17
        if remaining <= FEASIBILITY_TOL:
            break
    return margin


# ===========================================================================
# AGP (Algorithm 2, exact marginals)
# ===========================================================================

def agp(fleet: Fleet, users: Users, cand: np.ndarray, Q: np.ndarray,
        rnd=rounder("float64")) -> np.ndarray:
    """Algorithm 2 at every edge: place the feasible implementation of
    largest marginal σ gain until none fits; returns ``x [E, P]``."""
    E, P = fleet.E, fleet.P
    valid = cand >= 0
    x = np.zeros((E, P), dtype=bool)
    for e in range(E):
        rows = np.nonzero(users.edge == e)[0]
        Qe = np.zeros((rows.size, P))                      # dense [U_e, P]
        r_idx = np.broadcast_to(np.arange(rows.size)[:, None],
                                cand[rows].shape)
        ok = valid[rows]
        Qe[r_idx[ok], cand[rows][ok]] = Q[rows][ok]
        best = np.zeros(rows.size)
        remaining = float(fleet.R[e])
        while True:
            feasible = ~x[e] & (fleet.sm_r <= remaining + FEASIBILITY_TOL)
            if not feasible.any():
                break
            gains = rnd(np.maximum(Qe - best[:, None], 0.0).sum(axis=0))
            p = int(np.argmax(np.where(feasible, gains, -np.inf)))
            x[e, p] = True
            remaining -= float(fleet.sm_r[p])
            best = np.maximum(best, Qe[:, p])
    return x


def place(algo: str, fleet: Fleet, users: Users,
          rounding: str = "float64", **kw):
    """``(x, σ)`` of one instance under ``algo`` at ``rounding``."""
    rnd = rounder(rounding)
    cand, Q = pair_qos(fleet, users, rnd)
    x = {"egp": egp, "agp": agp}[algo](fleet, users, cand, Q, rnd, **kw)
    return x, sigma(fleet, users, x, cand, Q, rnd)
