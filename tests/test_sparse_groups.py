"""The sparse greedy's re-score over ``(edge, service)`` groups: the same
decisions as the host greedy and as today's arithmetic over every
candidate pair, with its loop touching only the picked service's users."""
import dataclasses
import re

import numpy as np
import pytest

from repro import obs
from repro.core import placement
from repro.core.candidates import impl_table_np
from repro.core.instance import synthetic_instance
from repro.core.placement import (FEASIBILITY_TOL, GROUP_CHUNK, egp_np,
                                  egp_place_sparse_jax)
from repro.kernels.qos_matrix.ops import qos_candidates_from_instance
from repro.workloads import evaluate_sparse
from repro.workloads.batched import sparse_evaluator


def _zipf_hotspot(seed):
    """Zipf(1.1) service popularity, and one edge in ten holding half the
    users: groups far larger than one chunk."""
    inst = synthetic_instance(3000, n_edges=10, n_services=30, seed=seed)
    rng = np.random.default_rng(seed + 100)
    pop = 1.0 / np.arange(1, inst.S + 1) ** 1.1
    u_service = rng.choice(inst.S, size=inst.U, p=pop / pop.sum())
    u_edge = np.where(rng.random(inst.U) < 0.5, 0,
                      rng.integers(1, inst.E, size=inst.U))
    return dataclasses.replace(inst, u_edge=u_edge, u_service=u_service)


def _no_eligible(seed):
    """The implementations of two services removed from the catalog: their
    users have no eligible implementation, and so no candidate."""
    inst = synthetic_instance(800, n_edges=6, n_services=20, seed=seed)
    keep = ~np.isin(inst.sm_service, (3, 11))
    return dataclasses.replace(
        inst, **{f: getattr(inst, f)[keep] for f in
                 ("sm_service", "sm_acc", "sm_k", "sm_w", "sm_r")})


def _unsorted_catalog(seed):
    """Implementations listed in a random order, not grouped by service."""
    inst = synthetic_instance(800, n_edges=6, n_services=20, seed=seed)
    perm = np.random.default_rng(seed + 200).permutation(inst.P)
    return dataclasses.replace(
        inst, **{f: getattr(inst, f)[perm] for f in
                 ("sm_service", "sm_acc", "sm_k", "sm_w", "sm_r")})


def _satisfiable(seed):
    """Light load, loose delay thresholds and ample storage: each edge stops
    once every one of its users is satisfied."""
    inst = synthetic_instance(150, n_edges=5, n_services=12, seed=seed)
    rng = np.random.default_rng(seed + 300)
    return dataclasses.replace(
        inst, u_alpha=rng.uniform(0.0, 0.5, inst.U),
        u_delta=np.full(inst.U, inst.delta_max), R=np.full(inst.E, 1000.0))


def _no_users(seed):
    """A tick no user reached: nothing to group, nothing placed."""
    inst = synthetic_instance(50, n_edges=3, n_services=5, seed=seed)
    none_i, none_f = np.zeros(0, np.int64), np.zeros(0)
    return dataclasses.replace(inst, u_edge=none_i, u_service=none_i,
                               u_alpha=none_f, u_delta=none_f)


def _pairs(inst, k=None):
    ji = inst.as_jax()
    table = impl_table_np(inst.sm_service, inst.S)
    cand_idx, cand_q = qos_candidates_from_instance(ji, table, k,
                                                    use_kernel=False)
    return cand_idx, cand_q, ji.u_edge, ji.sm_service, ji.sm_r, ji.R


class _Picks:
    """A decision sink that keeps each edge's (pick, placed) sequence."""

    def __init__(self):
        self.by_edge = {}

    def pick(self, *, edge, impl, placed, **_):
        self.by_edge.setdefault(int(edge), []).append((int(impl),
                                                        bool(placed)))


@pytest.mark.parametrize("make,seed", [
    (_zipf_hotspot, 0), (_zipf_hotspot, 1), (_no_eligible, 2),
    (_unsorted_catalog, 3), (_satisfiable, 9),
], ids=["zipf-hotspot-0", "zipf-hotspot-1", "no-eligible",
        "unsorted-catalog", "satisfiable"])
def test_sparse_greedy_matches_egp_np_pick_for_pick(make, seed, monkeypatch):
    inst = make(seed)
    picks = _Picks()
    monkeypatch.setattr(placement, "_DECISION_SINK", picks)
    x_host = egp_np(inst)
    monkeypatch.setattr(placement, "_DECISION_SINK", None)
    x, trace = egp_place_sparse_jax(*_pairs(inst), max_iters=inst.P + 1,
                                    with_trace=True)
    assert np.array_equal(np.asarray(x), x_host)
    n = int(trace["n_iters"])
    pick = np.asarray(trace["pick"])[:n]
    placed = np.asarray(trace["placed"])[:n]
    for e in range(inst.E):
        seq = [(int(p), bool(b)) for p, b in zip(pick[:, e], placed[:, e])
               if p >= 0]
        assert seq == picks.by_edge.get(e, []), e


def _pair_loop(cand_idx, cand_q, u_edge, sm_service, sm_r, R, max_iters):
    """Algorithm 3 in lock-step over every (user, candidate) pair, in
    float32: the arithmetic of the loop before the grouping."""
    cand_idx, u_edge, sm_service = (np.asarray(a) for a in
                                    (cand_idx, u_edge, sm_service))
    sm_r, R = np.asarray(sm_r, np.float32), np.asarray(R, np.float32)
    U, K = cand_idx.shape
    P, E = sm_service.size, R.size
    valid = cand_idx >= 0
    col = np.where(valid, cand_idx, P)
    q = np.where(valid, np.asarray(cand_q), 0.0).astype(np.float32)
    erow = np.repeat(u_edge[:, None], K, axis=1)

    def scatter(w):
        out = np.zeros((E, P + 1), np.float32)
        np.add.at(out, (erow, col), w.astype(np.float32))
        return out[:, :P]

    relevant = scatter(valid) > 0
    v = scatter(q)
    x = np.zeros((E, P), bool)
    considered = np.zeros((E, P), bool)
    satisfied = np.zeros(U, bool)
    remaining = R.copy()
    done = np.zeros(E, bool)
    rows = np.arange(E)
    it = n_rescores = 0
    while not done.all() and it < max_iters:
        cand = relevant & ~considered
        any_cand = cand.any(axis=1)
        p_star = np.argmax(np.where(cand, v, np.float32(-1e30)), axis=1)
        fits = sm_r[p_star] <= remaining + np.float32(FEASIBILITY_TOL)
        place = fits & any_cand & ~done
        x[rows, p_star] |= place
        remaining = remaining - np.where(place, sm_r[p_star], np.float32(0))
        if place.any():
            n_rescores += 1
            place_u = place[u_edge]
            qstar = np.where(col == p_star[u_edge][:, None], q, 0).sum(axis=1)
            unsat = place_u & ~satisfied
            diff = scatter(np.where(unsat[:, None] & valid,
                                    q - qstar[:, None], 0))
            sib = (sm_service[None, :] == sm_service[p_star][:, None]) \
                & ~considered & (np.arange(P)[None, :] != p_star[:, None]) \
                & relevant
            v = np.where(place[:, None] & sib, diff, v)
            satisfied |= place_u & (qstar >= np.float32(1.0 - 1e-6))
        considered[rows, p_star] |= any_cand
        n_unsat = np.bincount(u_edge, weights=~satisfied, minlength=E)
        done |= ~any_cand | (remaining <= np.float32(1e-6)) \
            | (n_unsat == 0) | (considered | ~relevant).all(axis=1)
        it += 1
    return x, it, n_rescores


@pytest.mark.parametrize("make,seed,k,blank", [
    (_zipf_hotspot, 4, None, False), (_zipf_hotspot, 4, None, True),
    (_unsorted_catalog, 5, 2, False), (_unsorted_catalog, 5, 1, True),
    (_satisfiable, 10, None, False), (_satisfiable, 11, 2, False),
    (_no_users, 12, None, False),
], ids=["k=M", "k=M-no-candidates", "k=2", "k=1-no-candidates",
        "k=M-satisfiable", "k=2-satisfiable", "no-users"])
def test_loop_counts_match_a_pair_loop(make, seed, k, blank):
    inst = make(seed)
    cand_idx, cand_q, *rest = _pairs(inst, k)
    if blank:
        # users whose every candidate slot is padding belong to no group,
        # and keep their edge from ever being all satisfied
        gone = np.random.default_rng(6).random(inst.U) < 0.05
        cand_idx = np.where(gone[:, None], -1, np.asarray(cand_idx))
        cand_q = np.where(gone[:, None], 0.0, np.asarray(cand_q))
    args = (cand_idx, cand_q, *rest)
    x_ref, n_iters, n_rescores = _pair_loop(*args, max_iters=inst.P + 1)
    x, info = placement._egp_place_sparse(
        *args, max_iters=inst.P + 1, use_kernel=False, with_trace=False)
    assert np.array_equal(np.asarray(x), x_ref)
    assert (int(info["n_iters"]), int(info["n_rescores"])) == \
        (n_iters, n_rescores)


def test_group_counter_is_the_placed_groups_sizes():
    inst = _no_eligible(7)
    args = _pairs(inst)
    _, trace = egp_place_sparse_jax(*args, max_iters=inst.P + 1,
                                    with_trace=True)
    n = int(trace["n_iters"])
    pick = np.asarray(trace["pick"])[:n]
    placed = np.asarray(trace["placed"])[:n]
    has_cand = (np.asarray(args[0]) >= 0).any(axis=1)
    expect = sum(
        int(((inst.u_edge == e) & has_cand
             & (inst.u_service == inst.sm_service[pick[i, e]])).sum())
        for i, e in zip(*np.nonzero(placed)))
    assert expect > placed.sum() > 0
    assert int(trace["n_group_users"]) == expect
    tr = obs.enable()
    try:
        evaluate_sparse([inst])
    finally:
        obs.disable()
    assert tr.counters["placement.greedy_group_users"] == expect


def _callee(op):
    return str(op.attributes["callee"]).lstrip("@")


def _size(value):
    dims = re.match(r"tensor<([0-9x]*?)x?[a-z]+[0-9]*>", str(value.type))
    return int(np.prod([int(d) for d in dims.group(1).split("x") if d]))


def test_loop_body_moves_no_user_wide_arrays():
    """No scatter, sort or gather inside the greedy's while loop moves U or
    more elements (a scatter's updates, a sort's operands, a gather's
    result): per-pick work scales with the placed groups, not with U."""
    U = 10**4
    inst = synthetic_instance(U, n_edges=4, n_services=20, seed=8)
    assert inst.E * inst.P < U
    module = sparse_evaluator(inst.P + 1, False).lower(
        *_pairs(inst)).compiler_ir("stablehlo")
    funcs = {str(op.attributes["sym_name"]).strip('"'): op
             for op in module.body.operations}

    def walk(op, seen):
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    yield child
                    name = child.operation.name
                    if name == "func.call" and _callee(child) not in seen:
                        seen.add(_callee(child))
                        yield from walk(funcs[_callee(child)], seen)
                    yield from walk(child, seen)

    main = funcs["main"]
    loops = [op for op in main.regions[0].blocks[0].operations
             if op.operation.name == "stablehlo.while"]
    assert len(loops) == 1
    body = loops[0].regions[1]
    moved = {}
    seen = set()
    for block in body.blocks:
        for top in block.operations:
            for op in [top, *walk(top, seen)]:
                name = op.operation.name
                if name == "stablehlo.scatter":
                    moved.setdefault(name, []).append(
                        _size(op.operands[len(op.operands) - 1]))
                elif name == "stablehlo.sort":
                    moved.setdefault(name, []).append(_size(op.operands[0]))
                elif name == "stablehlo.gather":
                    moved.setdefault(name, []).append(_size(op.results[0]))
    assert moved.get("stablehlo.scatter") and moved.get("stablehlo.gather")
    assert max(n for sizes in moved.values() for n in sizes) < U, moved
    # the re-score's pair gathers are one chunk of each edge's group
    assert max(moved["stablehlo.gather"]) >= GROUP_CHUNK * inst.E
