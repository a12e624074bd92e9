"""Top-k sparse candidate sets + lock-step sparse EGP: exactness vs the
dense float64 host path, np/jnp agreement, and the k < M lower bound."""
import numpy as np
import pytest

from repro.core import (
    egp_np,
    impl_table_np,
    max_impls_of,
    qos_matrix_np,
    sigma_np,
    sigma_sparse_np,
    synthetic_instance,
    topk_candidates_jnp,
    topk_candidates_np,
)
from repro.sweeps.shard import HOST_PARITY_ATOL
from repro.workloads import evaluate_host, evaluate_sparse, horizon


# ===========================================================================
# impl table / candidate selection
# ===========================================================================

def test_impl_table_lists_every_implementation_once():
    inst = synthetic_instance(200, seed=0)
    table = impl_table_np(inst.sm_service, inst.S)
    assert table.shape == (inst.S, max_impls_of(inst))
    listed = table[table >= 0]
    # every model appears exactly once, under its own service's row
    assert sorted(listed.tolist()) == list(range(inst.P))
    rows = np.repeat(np.arange(inst.S), table.shape[1])[table.ravel() >= 0]
    np.testing.assert_array_equal(inst.sm_service[listed], rows)


@pytest.mark.parametrize("k", [None, 1, 3])
def test_topk_np_matches_jnp(k):
    inst = synthetic_instance(300, seed=2)
    cand = topk_candidates_np(inst, k)
    table = impl_table_np(inst.sm_service, inst.S)
    ji, jt = inst.as_jax(), np.asarray(table)
    idx, q = topk_candidates_jnp(ji, jt, k)
    # same candidate set per user (k = M keeps table order, np sorts by
    # QoS — order is irrelevant to the sparse greedy), same QoS values
    np.testing.assert_array_equal(np.sort(np.asarray(idx, np.int64), axis=1),
                                  np.sort(cand.cand_idx, axis=1))
    np.testing.assert_allclose(np.sort(np.asarray(q, np.float64), axis=1),
                               np.sort(cand.cand_q, axis=1), atol=1e-5)
    assert cand.exact == (k is None or k >= max_impls_of(inst))


def test_candidates_cover_exactly_the_eligible_models():
    inst = synthetic_instance(150, seed=4)
    cand = topk_candidates_np(inst)  # k = M → exact
    Q = qos_matrix_np(inst)
    for u in range(inst.U):
        eligible = set(np.flatnonzero(inst.sm_service == inst.u_service[u]))
        got = set(cand.cand_idx[u][cand.cand_idx[u] >= 0].tolist())
        assert got == eligible
        for c, p in enumerate(cand.cand_idx[u]):
            if p >= 0:
                assert cand.cand_q[u, c] == pytest.approx(Q[u, p])


# ===========================================================================
# sparse EGP == dense host path (exactness at k = M)
# ===========================================================================

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_egp_matches_host_sigma(seed):
    inst = synthetic_instance(400, n_edges=5, seed=seed)
    vals, xs = evaluate_sparse([inst])
    host = evaluate_host([inst])
    np.testing.assert_allclose(np.asarray(vals), host,
                               atol=HOST_PARITY_ATOL)
    # and σ recomputed on the sparse placement agrees (not an equal-value
    # different-placement fluke)
    x = np.asarray(xs[0])[:inst.E, :inst.P]
    np.testing.assert_allclose(float(vals[0]), sigma_np(inst, x),
                               atol=HOST_PARITY_ATOL)
    used = (x * inst.sm_r[None, :]).sum(axis=1)
    assert np.all(used <= inst.R + 1e-5)


def test_sparse_egp_matches_host_on_scenario_mix():
    instances = []
    for name in ("steady", "flash_crowd", "mobility_churn"):
        instances += horizon(name, seed=0, n_ticks=2)
    vals, _ = evaluate_sparse(instances)
    host = evaluate_host(instances)
    np.testing.assert_allclose(np.asarray(vals), host,
                               atol=HOST_PARITY_ATOL)


def test_sparse_kernel_path_matches_ref_path():
    inst = synthetic_instance(200, seed=7)
    v_ref, x_ref = evaluate_sparse([inst], use_kernel=False)
    v_k, x_k = evaluate_sparse([inst], use_kernel=True)
    np.testing.assert_allclose(np.asarray(v_ref), np.asarray(v_k),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(x_ref[0]), np.asarray(x_k[0]))


def test_users_of_an_unimplemented_last_service_get_no_candidate():
    """The last service id loses its implementations: its users must get
    no candidate, not the row of the last implemented service."""
    import dataclasses

    inst = synthetic_instance(400, n_edges=5, seed=3)
    keep = inst.sm_service != inst.S - 1
    inst = dataclasses.replace(inst, **{
        f: getattr(inst, f)[keep]
        for f in ("sm_service", "sm_acc", "sm_k", "sm_w", "sm_r")})
    orphans = inst.u_service >= inst.S
    assert orphans.any()
    assert (topk_candidates_np(inst).cand_idx[orphans] == -1).all()
    _, xs = evaluate_sparse([inst])
    np.testing.assert_array_equal(np.asarray(xs[0]), egp_np(inst))


def test_evaluate_sparse_rejects_non_egp():
    inst = synthetic_instance(50, seed=0)
    with pytest.raises(ValueError, match="egp"):
        evaluate_sparse([inst], algo="agp")


# ===========================================================================
# σ over candidate pairs
# ===========================================================================

def test_sigma_sparse_np_matches_sigma_np_on_dense_placement():
    inst = synthetic_instance(250, seed=3)
    Q = qos_matrix_np(inst)
    x = egp_np(inst, Q)
    cand = topk_candidates_np(inst)  # exact
    assert sigma_sparse_np(inst, x, cand) == pytest.approx(sigma_np(inst, x))


def test_k_below_max_impls_is_valid_and_k_max_is_exact():
    """k < M restricts the greedy's candidate pool: the result is still a
    feasible placement with positive σ (greedy is a heuristic, so a
    *smaller* pool can land either side of the full-pool greedy — no
    ordering is asserted); k = M reproduces the dense host path exactly."""
    inst = synthetic_instance(300, seed=5)
    exact = float(evaluate_host([inst])[0])
    for k in (1, 2):
        v, xs = evaluate_sparse([inst], k=k)
        assert 0.0 < float(v[0]) <= inst.U  # σ is a sum of QoS ∈ [0, 1]
        x = np.asarray(xs[0])
        used = (x * inst.sm_r[None, :]).sum(axis=1)
        assert np.all(used <= inst.R + 1e-5)  # storage respected
    vM = float(np.asarray(evaluate_sparse([inst])[0])[0])
    assert vM == pytest.approx(exact, abs=HOST_PARITY_ATOL)  # k=M exact
