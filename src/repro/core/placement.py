"""Placement algorithms for PIES (§V of the paper).

Host (NumPy) implementations that follow the paper's pseudocode:

* :func:`egp_np`  — Efficient Greedy Placement (Algorithm 3).
* :func:`agp_np`  — Approximate Greedy Placement (Algorithm 2) with the
  exact-marginal vectorization (σ(P∪{p}) − σ(P) = Σ_u max(0, Q[u,p] −
  best_u), which is mathematically identical to recomputing OMS per
  candidate as the paper does, but O(U·P) per pick instead of O(U·P²)).
* :func:`agp_literal_np` — Algorithm 2 exactly as written (recomputes
  optimal scheduling for every candidate at every pick); kept to reproduce
  the paper's Fig. 3b runtime separation.
* :func:`sck_np`  — the knapsack-DP baseline ("SCK").
* :func:`rnd_np`  — random placement + random eligible scheduling ("RND").

JAX implementations (jit-able, fixed-shape, masked; the composable modules
the serving control plane uses):

* :func:`egp_place_jax`, :func:`agp_place_jax` — vmapped-over-edges masked
  ``lax.while_loop`` greedy selection over the QoS matrix.
* :func:`egp_place_sparse_jax`, :func:`sigma_sparse_jnp` — the same
  Algorithm 3 decisions driven from a top-k ``(user, candidate)`` pair set
  (:mod:`repro.core.candidates`), all edges advanced in lock-step by one
  joint ``lax.while_loop``; state is O(U·k + E·P) instead of the dense
  path's O(E·U·P), which is what makes 10⁵–10⁶-user ticks feasible. The
  users are grouped by ``(edge, service)`` once per call, and each pick's
  re-score visits only the group of its edge and service, the only users
  whose benefits or ``satisfied`` it can change: per-pick work scales with
  those groups, not with U.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from .instance import PIESInstance
from .qos import qos_matrix_np, eligibility_np
from .scheduling import oms_np, sigma_np, user_sum

__all__ = [
    "FEASIBILITY_TOL",
    "egp_np", "agp_np", "agp_literal_np", "sck_np", "rnd_np",
    "egp_place_jax", "agp_place_jax", "place_and_schedule",
    "egp_place_sparse_jax", "sigma_sparse_jnp",
    "sigma_upper_bound_np",
]

#: Shared feasibility slack for ``r_sm ≤ R̂`` checks. One constant for the
#: host (float64) and JAX (float32) paths: 1e-6 is representable at float32
#: resolution around typical storage magnitudes, so a boundary-cost model
#: (``r_sm == R̂`` exactly) is accepted or rejected identically by
#: :func:`agp_np` and :func:`_agp_one_edge` — they can never disagree on
#: which placements are feasible.
FEASIBILITY_TOL = 1e-6

#: Users of one ``(edge, service)`` group that the sparse greedy's re-score
#: visits at once, at every edge together; a larger group takes more chunks.
GROUP_CHUNK = 8

#: Decision-ledger hook. ``repro.obs.ledger.enable_ledger()`` installs a
#: :class:`~repro.obs.ledger.DecisionLedger` here (the core never imports
#: obs); the greedy pick loops book every consideration through it. The
#: disabled path is one global load + ``is None`` per placement call, and
#: the ledger is observational — picks are recorded, never influenced.
_DECISION_SINK = None


def sigma_upper_bound_np(inst: PIESInstance,
                         Q: Optional[np.ndarray] = None) -> float:
    """Per-user relaxation upper bound σ̄ on the optimum of Eq. (1).

    Every user is served by its best eligible implementation that would
    fit its edge's *whole* storage budget on its own — i.e. the LP/ILP
    with all coupling (shared budgets across services) relaxed away. By
    construction ``σ̄ ≥ OPT ≥ σ(x)`` for any feasible ``x``, so the
    Theorem-2 certificate ``σ(greedy) ≥ (1 − 1/e)·σ̄`` is strictly
    stronger than the guarantee against OPT (and, being a relaxation,
    σ̄ can overshoot — a ratio below the line flags a placement for
    inspection rather than refuting the theorem).
    """
    if Q is None:
        Q = qos_matrix_np(inst)
    fits = inst.sm_r[None, :] <= (inst.R[inst.u_edge][:, None]
                                  + FEASIBILITY_TOL)  # [U, P]
    # Q is already zero for ineligible (user, impl) pairs
    return float(np.where(fits, Q, 0.0).max(axis=1).sum())


# ===========================================================================
# Algorithm 3: Efficient Greedy Placement (EGP)
# ===========================================================================

def egp_np(inst: PIESInstance, Q: Optional[np.ndarray] = None) -> np.ndarray:
    """Efficient Greedy Placement — Algorithm 3, line-by-line.

    Per edge cloud: seed the benefit map ``v[(s,m)] = Σ_{u∈U_e} Q(u,s_u,m)``
    (lines 3–6); repeatedly take the highest-benefit unconsidered model
    (line 11), place it if it fits (lines 12–14), re-score the *sibling*
    implementations of the same service against the newly placed one over
    the not-yet-satisfied users (lines 15–16), mark it considered (17) and
    absorb fully-satisfied users into ``B`` (18–19); stop when storage is
    exhausted, everyone is satisfied, or all candidates were considered
    (line 20).
    """
    if Q is None:
        Q = qos_matrix_np(inst)
    x = np.zeros((inst.E, inst.P), dtype=bool)
    sink = _DECISION_SINK

    for e in range(inst.E):
        users = inst.users_of_edge(e)
        if users.size == 0:
            continue
        req_services = np.unique(inst.u_service[users])
        keys = np.nonzero(np.isin(inst.sm_service, req_services))[0]
        if keys.size == 0:
            continue
        Qe = Q[users]  # [|U_e|, P]
        v = {int(p): float(Qe[:, p].sum()) for p in keys}

        considered: set = set()           # A
        satisfied = np.zeros(users.size, dtype=bool)  # B (mask over users)
        remaining = float(inst.R[e])      # R̂
        if sink is not None:
            best = np.zeros(users.size)   # σ_u over placed impls at e

        while True:
            cand = [p for p in v if p not in considered]
            if not cand:
                break
            p_star = max(cand, key=lambda p: (v[p], -p))
            benefit = v[p_star]
            placed = inst.sm_r[p_star] <= remaining + FEASIBILITY_TOL
            if placed:
                x[e, p_star] = True
                remaining -= float(inst.sm_r[p_star])
                # lines 15–16: re-score sibling implementations of s*
                s_star = inst.sm_service[p_star]
                unsat = ~satisfied
                for p in keys:
                    p = int(p)
                    if (inst.sm_service[p] == s_star and p != p_star
                            and p not in considered):
                        v[p] = float(
                            (Qe[unsat, p] - Qe[unsat, p_star]).sum()
                        )
                # lines 18–19: users fully satisfied by (s*, m*)
                satisfied |= Qe[:, p_star] >= 1.0 - 1e-9
            considered.add(p_star)
            if sink is not None:
                gain = 0.0
                if placed:
                    # exact marginal: the gains over placed picks
                    # telescope to the realized σ of the edge
                    gain = float(np.maximum(Qe[:, p_star] - best,
                                            0.0).sum())
                    best = np.maximum(best, Qe[:, p_star])
                # rank 0 by construction: p_star is the benefit argmax
                sink.pick(edge=e, impl=p_star, benefit=benefit,
                          gain=gain, remaining=remaining,
                          n_candidates=len(cand), rank=0, placed=placed)
            if remaining <= FEASIBILITY_TOL or satisfied.all() or len(considered) == len(v):
                break
    return x


# ===========================================================================
# Algorithm 2: Approximate Greedy Placement (AGP)
# ===========================================================================

def agp_np(inst: PIESInstance, Q: Optional[np.ndarray] = None) -> np.ndarray:
    """Approximate Greedy Placement — Algorithm 2 with exact marginals.

    Identical picks to the literal pseudocode (argmax of σ(P ∪ {(e,(s,m))})
    over feasible candidates) but computes each marginal in closed form:
    adding model ``p`` at edge ``e`` improves only users in ``U_e`` whose
    current best QoS is below ``Q[u, p]``.
    """
    if Q is None:
        Q = qos_matrix_np(inst)
    x = np.zeros((inst.E, inst.P), dtype=bool)
    best = np.zeros(inst.U)  # σ_u under current placement

    for e in range(inst.E):
        users = inst.users_of_edge(e)
        remaining = float(inst.R[e])
        placed = np.zeros(inst.P, dtype=bool)
        while True:
            feasible = (~placed) & (inst.sm_r <= remaining + FEASIBILITY_TOL)
            if not feasible.any():
                break
            if users.size:
                gains = np.maximum(Q[users] - best[users, None], 0.0).sum(axis=0)
            else:
                gains = np.zeros(inst.P)
            gains = np.where(feasible, gains, -np.inf)
            p_star = int(np.argmax(gains))
            x[e, p_star] = True
            placed[p_star] = True
            remaining -= float(inst.sm_r[p_star])
            if users.size:
                best[users] = np.maximum(best[users], Q[users, p_star])
    return x


def agp_literal_np(inst: PIESInstance,
                   Q: Optional[np.ndarray] = None) -> np.ndarray:
    """Algorithm 2 exactly as printed: every candidate evaluated by running
    optimal scheduling on σ(P ∪ {(e,(s,m))}) from scratch. O(U·P²) per pick
    — this is the runtime the paper complains about in Fig. 3b."""
    if Q is None:
        Q = qos_matrix_np(inst)
    x = np.zeros((inst.E, inst.P), dtype=bool)
    for e in range(inst.E):
        remaining = float(inst.R[e])
        placed = np.zeros(inst.P, dtype=bool)
        while True:
            feasible = np.nonzero((~placed) & (inst.sm_r <= remaining + FEASIBILITY_TOL))[0]
            if feasible.size == 0:
                break
            best_val, best_p = -np.inf, -1
            for p in feasible:
                x[e, p] = True
                val = sigma_np(inst, x, Q)  # full optimal scheduling
                x[e, p] = False
                if val > best_val:
                    best_val, best_p = val, int(p)
            x[e, best_p] = True
            placed[best_p] = True
            remaining -= float(inst.sm_r[best_p])
    return x


# ===========================================================================
# Baselines: SCK (knapsack DP) and RND
# ===========================================================================

def sck_np(inst: PIESInstance, Q: Optional[np.ndarray] = None,
           resolution: int = 1) -> np.ndarray:
    """0/1-knapsack adaptation (the paper's "SCK" baseline).

    Per edge cloud: items are the individual service models, weights are
    their storage costs, values are their *standalone* total QoS
    ``Σ_{u∈U_e} Q(u, s_u, m)`` (Eq. 1 summed over covered users — ignoring
    that multiple implementations of one service overlap, which is exactly
    why SCK underperforms). Solved with the standard DP; scheduling is then
    done with OMS (Alg. 1), as in the paper.
    """
    if Q is None:
        Q = qos_matrix_np(inst)
    x = np.zeros((inst.E, inst.P), dtype=bool)
    weights_all = np.round(inst.sm_r * resolution).astype(np.int64)

    for e in range(inst.E):
        users = inst.users_of_edge(e)
        if users.size == 0:
            continue
        values_all = Q[users].sum(axis=0)
        items = np.nonzero(values_all > 0.0)[0]
        if items.size == 0:
            continue
        cap = int(np.floor(inst.R[e] * resolution))
        dp = np.zeros(cap + 1)
        choice = np.zeros((items.size, cap + 1), dtype=bool)
        for i, p in enumerate(items):
            w, val = int(weights_all[p]), float(values_all[p])
            if w > cap:
                continue
            cand = dp[: cap - w + 1] + val
            upd = cand > dp[w:]
            choice[i, w:] = upd
            dp[w:] = np.where(upd, cand, dp[w:])
        # backtrack
        c = cap
        for i in range(items.size - 1, -1, -1):
            if choice[i, c]:
                p = items[i]
                x[e, p] = True
                c -= int(weights_all[p])
    return x


def rnd_np(inst: PIESInstance, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Random placement + random eligible scheduling baseline.

    Returns ``(x, y)`` — unlike the greedy algorithms, RND also randomizes
    the schedule (uniform over placed implementations of the requested
    service; −1 if none).
    """
    rng = np.random.default_rng(seed)
    x = np.zeros((inst.E, inst.P), dtype=bool)
    for e in range(inst.E):
        remaining = float(inst.R[e])
        for p in rng.permutation(inst.P):
            if inst.sm_r[p] <= remaining + FEASIBILITY_TOL:
                x[e, p] = True
                remaining -= float(inst.sm_r[p])
    elig = eligibility_np(inst) & x[inst.u_edge]
    y = np.full(inst.U, -1, dtype=np.int64)
    for u in range(inst.U):
        opts = np.nonzero(elig[u])[0]
        if opts.size:
            y[u] = int(rng.choice(opts))
    return x, y


# ===========================================================================
# JAX implementations — fixed-shape, masked, vmapped over edge clouds
# ===========================================================================

def _agp_one_edge(Q, umask, sm_r, R_e, max_iters):
    """Greedy exact-marginal placement for a single edge (jnp, masked)."""
    import jax
    import jax.numpy as jnp

    P = Q.shape[1]
    Qe = Q * umask[:, None]  # zero out other edges' users

    def cond(state):
        _, _, _, _, done = state
        return ~done

    def body(state):
        x_e, best, remaining, it, done = state
        feasible = (~x_e) & (sm_r <= remaining + FEASIBILITY_TOL)
        any_feasible = feasible.any()
        gains = user_sum(jnp.maximum(Qe - best[:, None], 0.0))
        gains = jnp.where(feasible, gains, -jnp.inf)
        p_star = jnp.argmax(gains)
        do = any_feasible & ~done
        x_e = x_e.at[p_star].set(jnp.where(do, True, x_e[p_star]))
        remaining = remaining - jnp.where(do, sm_r[p_star], 0.0)
        best = jnp.where(do, jnp.maximum(best, Qe[:, p_star]), best)
        it = it + 1
        done = done | ~any_feasible | (it >= max_iters)
        return x_e, best, remaining, it, done

    U = Q.shape[0]
    init = (jnp.zeros(P, bool), jnp.zeros(U, jnp.float32),
            R_e.astype(jnp.float32), jnp.int32(0), jnp.bool_(False))
    x_e, *_ = jax.lax.while_loop(cond, body, init)
    return x_e


def agp_place_jax(Q, elig, u_edge, sm_r, R, *, max_iters: int = 256):
    """jit-able AGP over all edges. ``Q`` [U,P] float32 (pre-masked by
    eligibility or not — it is re-masked here), returns x [E,P] bool."""
    import jax
    import jax.numpy as jnp

    E = R.shape[0]
    Qm = jnp.where(elig, Q, 0.0)
    umask = (u_edge[None, :] == jnp.arange(E)[:, None]).astype(Qm.dtype)
    fn = functools.partial(_agp_one_edge, Qm, sm_r=sm_r, max_iters=max_iters)
    return jax.vmap(lambda m, r: fn(m, R_e=r))(umask, R)


def _egp_one_edge(Q, umask, sm_service, sm_r, R_e, relevant, max_iters):
    """Algorithm 3 for a single edge (jnp, masked)."""
    import jax
    import jax.numpy as jnp

    U, P = Q.shape
    Qe = Q * umask[:, None]
    NEG = jnp.float32(-1e30)

    def cond(state):
        return ~state[-1]

    def body(state):
        x_e, v, considered, satisfied, remaining, it, done = state
        cand = relevant & ~considered
        any_cand = cand.any()
        p_star = jnp.argmax(jnp.where(cand, v, NEG))
        fits = sm_r[p_star] <= remaining + FEASIBILITY_TOL
        place = fits & any_cand & ~done
        x_e = x_e.at[p_star].set(x_e[p_star] | place)
        remaining = remaining - jnp.where(place, sm_r[p_star], 0.0)
        # lines 15–16: re-score unconsidered siblings of s* over unsatisfied
        q_star = Qe[:, p_star]
        unsat = (umask > 0) & ~satisfied
        diff = user_sum(jnp.where(unsat[:, None], Q - q_star[:, None], 0.0))
        sib = (sm_service == sm_service[p_star]) & ~considered \
            & (jnp.arange(P) != p_star) & relevant
        v = jnp.where(place & sib, diff, v)
        satisfied = satisfied | (place & (umask > 0) & (q_star >= 1.0 - 1e-6))
        considered = considered.at[p_star].set(considered[p_star] | any_cand)
        it = it + 1
        all_sat = (satisfied | (umask == 0)).all()
        all_cons = (considered | ~relevant).all()
        done = done | ~any_cand | (remaining <= 1e-6) | all_sat | all_cons \
            | (it >= max_iters)
        return x_e, v, considered, satisfied, remaining, it, done

    v0 = user_sum(Qe)
    init = (jnp.zeros(P, bool), v0, jnp.zeros(P, bool), jnp.zeros(U, bool),
            R_e.astype(jnp.float32), jnp.int32(0), jnp.bool_(False))
    x_e, *_ = jax.lax.while_loop(cond, body, init)
    return x_e


def egp_place_jax(Q, elig, u_edge, u_service, sm_service, sm_r, R, n_services,
                  *, max_iters: int = 512):
    """jit-able EGP over all edges: returns x [E, P] bool."""
    import jax
    import jax.numpy as jnp

    E = R.shape[0]
    Qm = jnp.where(elig, Q, 0.0).astype(jnp.float32)
    umask = (u_edge[None, :] == jnp.arange(E)[:, None]).astype(jnp.float32)
    # relevant[e, p] ⇔ some user covered by e requests service of p
    req = jnp.zeros((E, n_services), bool).at[u_edge, u_service].set(True)
    relevant = req[:, sm_service]  # [E, P]

    def run(m, r, rel):
        return _egp_one_edge(Qm, m, sm_service, sm_r, r, rel, max_iters)

    return jax.vmap(run)(umask, R, relevant)


def egp_place_sparse_jax(cand_idx, cand_q, u_edge, sm_service, sm_r, R,
                         *, max_iters: int = 512, use_kernel: bool = False,
                         with_trace: bool = False):
    """Algorithm 3 over a top-k sparse candidate set, all edges in lock-step.

    Takes the ``(cand_idx, cand_q) [U, k]`` pairs from
    :func:`repro.core.candidates.topk_candidates_jnp` instead of a dense
    ``[U, P]`` QoS matrix. One joint ``lax.while_loop`` advances every edge
    by one greedy pick per iteration (edges that finish early are masked by
    ``done``), so the working set is the O(E·P) greedy state plus O(U·k)
    candidate pairs — never the dense path's per-edge O(E·U·P) masked QoS
    copies. With ``k ≥ M`` (every eligible implementation kept) the picks,
    tie-breaks, and stop conditions are *identical* to
    :func:`egp_place_jax` / :func:`egp_np`: ineligible users contribute 0
    to every benefit sum in the dense path, so dropping them changes
    nothing; with ``k < M`` this is the documented top-k approximation.

    ``use_kernel=True`` routes the per-iteration masked per-edge argmax
    through the Pallas ``greedy_argmax`` kernel
    (:mod:`repro.kernels.qos_matrix`); the default uses the identical jnp
    reduction (interpret-mode Pallas inside a while_loop is slow on CPU).

    Every candidate of a user implements that user's own service, so a
    pick ``p*`` at edge ``e`` changes benefits (lines 15–16) and
    ``satisfied`` (lines 18–19) only through the users of ``e`` who
    request ``svc(p*)``. The users are sorted once by ``(edge, service)``,
    each such group a slice of the sorted order, and the re-score visits
    each placing edge's group, :data:`GROUP_CHUNK` users at a time at
    every edge together, as many chunks as the largest group needs; a
    per-edge count of unsatisfied users is carried for the stop test. The
    sums are over the same float32 terms as over every pair; only their
    order differs.

    The program's phases carry ``jax.named_scope`` tags, so a profiler
    trace (or the compiled HLO's ``op_name`` metadata) attributes device
    time to them: ``greedy.init`` (the grouping, the pair layout and the
    initial benefit scatters), and per iteration ``greedy.pick`` (lines
    11–14), ``greedy.rescore`` (the ``lax.cond`` of lines 15–19 over the
    placing edges' groups) and ``greedy.stop_test`` (line 17's
    ``considered`` update and line 20).

    ``with_trace=True`` additionally returns a per-iteration decision
    trace for the observability ledger: ``[max_iters, E]`` arrays of the
    pick (``impl``, −1 where an edge had no candidate / was done), its
    benefit, exact marginal gain (booked in f32 against a per-user
    ``best`` carry — gains telescope to ``sigma_sparse_jnp`` of the
    result up to f32 summation, documented tolerance ~1e-3 relative),
    the post-pick remaining budget, the candidate count, and the placed
    mask. The traced and untraced paths make **identical decisions** —
    the trace arrays are write-only extensions of the loop carry.

    Returns ``x [E, P]`` bool (or ``(x, trace_dict)`` with
    ``with_trace=True``; the trace also holds the loop's counts
    ``n_iters``, ``n_rescores`` and ``n_group_users``, the users the
    re-scores visited: the placed picks' group sizes, summed).
    """
    x, info = _egp_place_sparse(
        cand_idx, cand_q, u_edge, sm_service, sm_r, R, max_iters=max_iters,
        use_kernel=use_kernel, with_trace=with_trace)
    return (x, info) if with_trace else x


def _egp_place_sparse(cand_idx, cand_q, u_edge, sm_service, sm_r, R, *,
                      max_iters: int, use_kernel: bool, with_trace: bool):
    """:func:`egp_place_sparse_jax`'s program, returning ``(x, info)``:
    ``info["n_iters"]`` (iterations run), ``info["n_rescores"]``
    (iterations in which some edge placed and the re-score ran) and
    ``info["n_group_users"]`` (users the re-scores visited: the sizes of
    the placed picks' ``(edge, service)`` groups, summed over the loop),
    int32 scalars, plus the decision trace's arrays with ``with_trace``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    U, K = cand_q.shape
    P = sm_service.shape[0]
    E = R.shape[0]
    G = max(1, min(GROUP_CHUNK, U))
    NEG = jnp.float32(-1e30)

    def scatter_ep(w):
        """Σ over (user, candidate) pairs into the [E, P] model grid."""
        out = jnp.zeros((E, P + 1), jnp.float32)
        out = out.at[erow[None, :], col].add(w)
        return out[:, :P]

    with jax.named_scope("greedy.init"):
        svc = sm_service.astype(jnp.int32)
        p_arange = jnp.arange(P)
        e_arange = jnp.arange(E)
        # A user's candidates all implement its own service, so the largest
        # candidate index (valid wherever any is) names it; a user with no
        # candidate gets service P and so belongs to no group. Users sorted
        # by the key edge·(P + 1) + service hold each (edge, service) group
        # as one slice. Keys fit int32 while E·(P + 1) < 2³¹.
        idx_t = cand_idx.T.astype(jnp.int32)
        top = idx_t.max(axis=0)
        svc_u = jnp.where(top >= 0, svc[jnp.clip(top, 0, None)], P)
        key = u_edge.astype(jnp.int32) * (P + 1) + svc_u
        skey, perm = lax.sort((key, jnp.arange(U, dtype=jnp.int32)),
                              num_keys=1)
        # Pairs are held candidate-major, [K, U], so that U lies along the
        # TPU's 128-lane axis. Held [U, K], every gather and scatter over
        # them pads K to 128 lanes and takes minutes to compile at U = 10⁶.
        idx_s = idx_t[:, perm]
        valid = idx_s >= 0
        # Sentinel column P absorbs scatters from padded candidate slots.
        col = jnp.where(valid, idx_s, P)
        qpair = jnp.where(valid, cand_q.T[:, perm], 0.0).astype(jnp.float32)
        erow = skey // (P + 1)
        sm_r = sm_r.astype(jnp.float32)
        relevant = scatter_ep(valid.astype(jnp.float32)) > 0.0  # [E, P]
        # lines 3–6: v[(s,m)] = Σ_{u∈U_e} Q(u,s_u,m)
        v0 = scatter_ep(qpair)
        # Each implementation's rank among its service's: within a group
        # (one service) a rank names a candidate's column, so the loop
        # keeps ranks (−1 where padded) and not columns.
        rank = ((svc[None, :] == svc[:, None])
                & (p_arange[None, :] < p_arange[:, None])).sum(axis=1)
        rank = rank.astype(jnp.int32)
        rk = jnp.append(rank, -1)[col]
        n_blocks = rank.max() // K + 1   # blocks of K ranks a service spans
        # start of each key's slice in the sorted order: group (e, s) is
        # [start[e·(P+1) + s], start[e·(P+1) + s + 1])
        start = jnp.cumsum(jnp.zeros(E * (P + 1) + 1, jnp.int32)
                           .at[key + 1].add(1))
        # users at each edge, those without a candidate among them
        e_lo = start[jnp.arange(E + 1) * (P + 1)]
        n_unsat0 = e_lo[1:] - e_lo[:-1]

    def masked_argmax(v, cand):
        if use_kernel:
            from repro.kernels.qos_matrix.ops import greedy_argmax
            _, idx = greedy_argmax(v, cand.astype(jnp.float32),
                                   use_kernel=True)
            return jnp.clip(idx, 0, None)
        return jnp.argmax(jnp.where(cand, v, NEG), axis=1)

    def skip(arg):
        return arg + (jnp.zeros(E, jnp.float32),)   # no gains

    def rescore(arg, hit, place, considered):
        """Lines 15–19 at every placing edge, over the users of its
        ``(edge, svc(p*))`` group alone: only they can have a pair with a
        sibling of p*, and only their ``satisfied`` can change. Groups are
        visited G users at a time, as many chunks as the largest needs."""
        if U == 0:      # no candidate, so no pick is ever placed
            return skip(arg)
        v, satisfied, n_unsat, n_group, best_u = arg
        s_star = jnp.where(hit, svc, 0).sum(axis=1)
        r_star = jnp.where(hit, rank, 0).sum(axis=1)
        bounds = start[(e_arange * (P + 1) + s_star)[:, None]
                       + jnp.arange(2)]                          # [E, 2]
        lo = bounds[:, 0]
        size = jnp.where(place, bounds[:, 1] - lo, 0)
        n_chunks = (size.max() + G - 1) // G
        slot = jnp.arange(G)[:, None]

        def chunk(j, carry):
            diff, satisfied, n_unsat, best_u, gain = carry
            first = lo + j * G
            base = jnp.clip(first, 0, U - G)    # window kept in bounds
            pos = base[None, :] + slot                            # [G, E]
            member = (pos >= first) & (pos < lo + size)
            rk_m = rk[:, pos]                                     # [K, G, E]
            q_m = qpair[:, pos]
            sat_m = satisfied[pos]
            qstar = jnp.where(rk_m == r_star, q_m, 0.0).sum(axis=0)
            unsat = member & ~sat_m
            # lines 15–16: v[p] = Σ_unsat (Q[u,p] − Q[u,p*]), by rank
            w = jnp.where(unsat, q_m - qstar, 0.0)

            def block(b, diff):
                r = b * K + jnp.arange(K)
                by_rank = jnp.where(rk_m[None] == r[:, None, None, None],
                                    w[None], 0.0).sum(axis=(1, 2))  # [K, E]
                of_rank = rank[None, :] == r[:, None]               # [K, P]
                return diff + jnp.where(of_rank[:, None, :],
                                        by_rank[:, :, None], 0.0).sum(axis=0)

            diff = lax.fori_loop(0, n_blocks, block, diff)
            # lines 18–19: users fully satisfied by (s*, m*)
            newly = unsat & (qstar >= 1.0 - 1e-6)
            at = jnp.where(member, pos, U)     # U: out of bounds, dropped
            satisfied = satisfied.at[at].set(sat_m | newly, mode="drop")
            n_unsat = n_unsat - newly.sum(axis=0)
            if with_trace:
                # exact marginal per placed pick, booked before best_u moves
                b_m = best_u[pos]
                gain = gain + jnp.where(member, jnp.maximum(qstar - b_m, 0.0),
                                        0.0).sum(axis=0)
                best_u = best_u.at[at].set(jnp.maximum(b_m, qstar),
                                           mode="drop")
            return diff, satisfied, n_unsat, best_u, gain

        diff, satisfied, n_unsat, best_u, gain = lax.fori_loop(
            0, n_chunks, chunk,
            (jnp.zeros((E, P), jnp.float32), satisfied, n_unsat, best_u,
             jnp.zeros(E, jnp.float32)))
        sib = (svc[None, :] == s_star[:, None]) & ~considered & ~hit \
            & relevant
        v = jnp.where(place[:, None] & sib, diff, v)
        return v, satisfied, n_unsat, n_group + size.sum(), best_u, gain

    def cond(state):
        # `it` and `done` sit at fixed positions in both carry layouts
        # (with and without the trace extension)
        with jax.named_scope("greedy.stop_test"):
            done, it = state[-1], state[5]
            return (~done.all()) & (it < max_iters)

    def body(state):
        if with_trace:
            (x, v, considered, satisfied, remaining, it, n_rescore, n_group,
             n_unsat, best_u, tr, done) = state
        else:
            (x, v, considered, satisfied, remaining, it, n_rescore, n_group,
             n_unsat, done) = state
            best_u = jnp.zeros(0, jnp.float32)   # no gains to book
        with jax.named_scope("greedy.pick"):
            cand = relevant & ~considered
            any_cand = cand.any(axis=1)                       # [E]
            p_star = masked_argmax(v, cand)                   # [E] line 11
            # one-hot of each edge's pick: [E, P] selects, not gathers or
            # scatters, read and write the pick's entries
            hit = p_arange[None, :] == p_star[:, None]
            cost = jnp.where(hit, sm_r, 0.0).sum(axis=1)
            fits = cost <= remaining + FEASIBILITY_TOL
            place = fits & any_cand & ~done                   # lines 12–14
            active = any_cand & ~done  # edges actually picking this iter
            benefit = jnp.where(hit, v, NEG).max(axis=1)
            x = x | (hit & place[:, None])
            remaining = remaining - jnp.where(place, cost, 0.0)

        with jax.named_scope("greedy.rescore"):
            placed_any = place.any()
            v, satisfied, n_unsat, n_group, best_u, gain_e = jax.lax.cond(
                placed_any,
                functools.partial(rescore, hit=hit, place=place,
                                  considered=considered),
                skip, (v, satisfied, n_unsat, n_group, best_u))
            n_rescore = n_rescore + placed_any.astype(jnp.int32)
            if with_trace:
                t_pick, t_place, t_ben, t_gain, t_rem, t_ncand = tr
                tr = (
                    t_pick.at[it].set(jnp.where(active, p_star, -1)),
                    t_place.at[it].set(place),
                    t_ben.at[it].set(jnp.where(active, benefit, 0.0)),
                    t_gain.at[it].set(gain_e),
                    t_rem.at[it].set(remaining),
                    t_ncand.at[it].set(cand.sum(axis=1).astype(jnp.int32)),
                )
        with jax.named_scope("greedy.stop_test"):
            considered = considered | (hit & any_cand[:, None])  # line 17
            all_sat = n_unsat == 0
            all_cons = (considered | ~relevant).all(axis=1)
            # line 20 — same stop conditions (and tolerances) as
            # _egp_one_edge
            done = done | ~any_cand | (remaining <= 1e-6) | all_sat \
                | all_cons
            it = it + 1
        core = (x, v, considered, satisfied, remaining, it, n_rescore,
                n_group, n_unsat)
        if with_trace:
            return core + (best_u, tr, done)
        return core + (done,)

    with jax.named_scope("greedy.init"):
        init_core = (jnp.zeros((E, P), bool), v0, jnp.zeros((E, P), bool),
                     jnp.zeros(U, bool), R.astype(jnp.float32),
                     jnp.int32(0), jnp.int32(0), jnp.int32(0), n_unsat0)
        if with_trace:
            tr0 = (jnp.full((max_iters, E), -1, jnp.int32),
                   jnp.zeros((max_iters, E), bool),
                   jnp.zeros((max_iters, E), jnp.float32),
                   jnp.zeros((max_iters, E), jnp.float32),
                   jnp.zeros((max_iters, E), jnp.float32),
                   jnp.zeros((max_iters, E), jnp.int32))
            init = init_core + (jnp.zeros(U, jnp.float32), tr0,
                                jnp.zeros(E, bool))
        else:
            init = init_core + (jnp.zeros(E, bool),)
    out = jax.lax.while_loop(cond, body, init)
    info = {"n_iters": out[5], "n_rescores": out[6],
            "n_group_users": out[7]}
    if with_trace:
        tr = out[10]
        info.update(pick=tr[0], placed=tr[1], benefit=tr[2], gain=tr[3],
                    remaining=tr[4], n_candidates=tr[5])
    return out[0], info


def sigma_sparse_jnp(cand_idx, cand_q, u_edge, x):
    """σ (Eq. 9 with OMS folded in) over candidate pairs: each user gets its
    best *placed* candidate at its own edge. Exact vs
    :func:`repro.core.scheduling.sigma_jnp` when the candidate set kept
    every eligible implementation (``k ≥ M``). Its ops carry the
    ``sigma`` scope."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("sigma"):
        idx_t = cand_idx.T            # [K, U]: see egp_place_sparse_jax
        valid = idx_t >= 0
        placed = x[u_edge[None, :], jnp.clip(idx_t, 0, None)] & valid
        return jnp.where(placed, cand_q.T, 0.0).max(axis=0).sum()


def place_and_schedule(inst: PIESInstance, algo: str = "egp", seed: int = 0,
                       Q: Optional[np.ndarray] = None):
    """Convenience host entry point: returns ``(x, y, objective_value)``."""
    if Q is None:
        Q = qos_matrix_np(inst)
    if algo == "egp":
        x = egp_np(inst, Q)
    elif algo == "agp":
        x = agp_np(inst, Q)
    elif algo == "agp_literal":
        x = agp_literal_np(inst, Q)
    elif algo == "sck":
        x = sck_np(inst, Q)
    elif algo == "rnd":
        x, y = rnd_np(inst, seed)
        from .scheduling import schedule_value_np
        return x, y, schedule_value_np(inst, y, Q)
    elif algo == "opt":
        from .opt import opt_np
        x = opt_np(inst, Q)
    else:
        raise ValueError(f"unknown algorithm {algo!r}")
    y, value = oms_np(inst, x, Q)
    if _DECISION_SINK is not None and algo == "egp":
        # close the ledger record with the Theorem-2 certificate:
        # σ(greedy) vs (1 − 1/e) · σ̄ (relaxation upper bound)
        _DECISION_SINK.end(sigma=value,
                           sigma_bound=sigma_upper_bound_np(inst, Q))
    return x, y, value
