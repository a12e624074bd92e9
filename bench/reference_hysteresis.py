"""The plain reference of the hysteresis placer: Algorithm 3 (EGP) with a
stickiness bonus on the implementations resident at each edge, and the
tick's loads and value, in float64 NumPy.

A control tick starts from the resident set the previous tick left. Each
resident implementation's benefit carries the bonus: its initial benefit
(lines 3-6) and every sibling re-score (lines 15-16) add it, and so does
nothing else. The tick's loads are the placed implementations that were
not resident; its value is σ − switching cost · loads.

Like ``bench/reference.py``, whose draws, QoS and σ it uses, it imports
nothing of the program under test, and it runs edge by edge over
``(edge, service)`` groups, each step taking the best unconsidered
implementation among those that fit: an implementation that does not fit
never fits later, so the placement is Algorithm 3's, pick for pick among
placed implementations.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from bench import reference as ref


def egp_biased(fleet: ref.Fleet, users: ref.Users, cand: np.ndarray,
               Q: np.ndarray, resident: np.ndarray, bonus: float,
               rnd=ref.rounder("float64"),
               margins: Optional[np.ndarray] = None) -> np.ndarray:
    """Algorithm 3 at every edge with ``bonus`` added to the benefit of
    each implementation ``resident [E, P]`` marks; returns ``x [E, P]``.
    With ``margins`` (``[E]``), each edge's smallest gap between the
    biased benefit of a placed implementation and the next best that also
    fitted."""
    E, P, S = fleet.E, fleet.P, fleet.n_services
    table = ref.impl_table(fleet)
    n_impl = (table >= 0).sum(axis=1)
    first = table[:, 0]
    key = users.edge.astype(np.int64) * S + users.service
    order = np.argsort(key, kind="stable")
    Qs = Q[order]
    bounds = np.concatenate([[0], np.cumsum(np.bincount(key, minlength=E * S))])
    valid = cand >= 0
    flat = (users.edge[:, None] * P + np.where(valid, cand, 0))[valid]
    v0 = np.bincount(flat, weights=Q[valid], minlength=E * P).reshape(E, P)
    rel = (np.bincount(flat, minlength=E * P) > 0).reshape(E, P)
    bias = np.where(resident, float(bonus), 0.0)
    x = np.zeros((E, P), dtype=bool)
    if margins is None:
        margins = np.empty(E)
    for e in range(E):
        margins[e] = _edge(e, x[e], rnd(v0[e] + bias[e]), bias[e], rel[e],
                           fleet, first, n_impl, Qs, bounds, S, rnd)
    return x


def _edge(e, x_e, v, bias, relevant, fleet, first, n_impl, Qs, bounds, S,
          rnd) -> float:
    sat: Dict[int, np.ndarray] = {}          # B, per service group at e
    n_users = int(bounds[(e + 1) * S] - bounds[e * S])
    n_sat = 0
    open_ = relevant.copy()                  # relevant and not considered
    remaining = float(fleet.R[e])            # R̂
    margin = np.inf
    while n_sat < n_users:
        fits = open_ & (fleet.sm_r <= remaining + ref.FEASIBILITY_TOL)
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
        sl = slice(int(first[s]), int(first[s]) + m)
        Qg = Qs[lo:hi, :m]                   # the group's users × impls of s
        done = sat.setdefault(s, np.zeros(hi - lo, dtype=bool))
        j = p - int(first[s])
        # lines 15-16: re-score unconsidered siblings over unsatisfied
        # users, each with its own bonus
        diff = rnd((Qg[~done] - Qg[~done, j:j + 1]).sum(axis=0) + bias[sl])
        sib = open_[sl].copy()
        sib[j] = False
        v[sl] = np.where(sib, diff, v[sl])
        # lines 18-19: users fully satisfied by the placed implementation
        newly = ~done & (Qg[:, j] >= 1.0 - ref.SATISFIED_TOL)
        done |= newly
        n_sat += int(newly.sum())
        open_[p] = False                                    # line 17
        if remaining <= ref.FEASIBILITY_TOL:
            break
    return margin


def place(fleet: ref.Fleet, users: ref.Users, resident: np.ndarray,
          bonus: float, switching_cost: float, rounding: str = "float64",
          margins: Optional[np.ndarray] = None) -> Dict:
    """One control tick from ``resident``: ``x``, ``sigma``, ``new_loads``
    (``x & ~resident``), ``loads`` and ``value`` (σ − switching cost ·
    loads), at ``rounding``."""
    rnd = ref.rounder(rounding)
    cand, Q = ref.pair_qos(fleet, users, rnd)
    x = egp_biased(fleet, users, cand, Q, resident, bonus, rnd, margins)
    sigma = ref.sigma(fleet, users, x, cand, Q, rnd)
    new_loads = x & ~resident
    loads = int(new_loads.sum())
    return {"x": x, "sigma": sigma, "new_loads": new_loads, "loads": loads,
            "value": sigma - switching_cost * loads}
