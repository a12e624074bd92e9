"""OMS (Algorithm 1 / Theorem 2) tests: per-user argmax is optimal."""
import itertools

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import (
    eligibility_np,
    oms_np,
    qos_matrix_np,
    schedule_value_np,
    sigma_np,
    synthetic_instance,
)


def _random_placement(inst, rng):
    x = np.zeros((inst.E, inst.P), dtype=bool)
    for e in range(inst.E):
        rem = inst.R[e]
        for p in rng.permutation(inst.P):
            if inst.sm_r[p] <= rem and rng.random() < 0.5:
                x[e, p] = True
                rem -= inst.sm_r[p]
    return x


@settings(deadline=None, max_examples=25)
@given(st.integers(0, 10_000))
def test_oms_beats_every_explicit_schedule(seed):
    """Theorem 2: OMS value ≥ value of any feasible schedule (enumerated)."""
    rng = np.random.default_rng(seed)
    inst = synthetic_instance(6, n_edges=2, n_services=3, max_impls=2, seed=seed)
    Q = qos_matrix_np(inst)
    x = _random_placement(inst, rng)
    y_star, v_star = oms_np(inst, x, Q)

    elig = eligibility_np(inst) & x[inst.u_edge]
    per_user_options = [
        [-1] + list(np.nonzero(elig[u])[0]) for u in range(inst.U)
    ]
    best = max(
        schedule_value_np(inst, np.array(combo), Q)
        for combo in itertools.product(*per_user_options)
    )
    assert v_star >= best - 1e-9
    np.testing.assert_allclose(v_star, best, atol=1e-9)


@settings(deadline=None, max_examples=25)
@given(st.integers(0, 10_000))
def test_oms_value_equals_sigma(seed):
    rng = np.random.default_rng(seed)
    inst = synthetic_instance(40, n_edges=4, n_services=10, seed=seed)
    Q = qos_matrix_np(inst)
    x = _random_placement(inst, rng)
    _, v = oms_np(inst, x, Q)
    np.testing.assert_allclose(v, sigma_np(inst, x, Q), atol=1e-9)


def test_oms_respects_placement_and_service():
    inst = synthetic_instance(50, seed=3)
    Q = qos_matrix_np(inst)
    rng = np.random.default_rng(0)
    x = _random_placement(inst, rng)
    y, _ = oms_np(inst, x, Q)
    for u in range(inst.U):
        if y[u] >= 0:
            # constraint (7c): model placed on covering edge
            assert x[inst.u_edge[u], y[u]]
            # scheduled model implements the requested service
            assert inst.sm_service[y[u]] == inst.u_service[u]


def test_oms_empty_placement_drops_everyone():
    inst = synthetic_instance(20, seed=1)
    x = np.zeros((inst.E, inst.P), dtype=bool)
    y, v = oms_np(inst, x)
    assert v == 0.0 and np.all(y == -1)


def test_oms_jnp_matches_np():
    import jax.numpy as jnp
    from repro.core import oms_jnp, eligibility_jnp, qos_matrix_jnp

    rng = np.random.default_rng(5)
    inst = synthetic_instance(64, n_edges=4, seed=5)
    Q = qos_matrix_np(inst)
    x = _random_placement(inst, rng)
    y_np, v_np = oms_np(inst, x, Q)

    ji = inst.as_jax()
    y_j, qos_j = oms_jnp(qos_matrix_jnp(ji), eligibility_jnp(ji),
                         ji.u_edge, jnp.asarray(x))
    np.testing.assert_allclose(float(qos_j.sum()), v_np, rtol=1e-5)
    # schedules may differ only on exact ties; values per user must match
    per_user_np = np.where(y_np >= 0, Q[np.arange(inst.U), np.maximum(y_np, 0)], 0.0)
    np.testing.assert_allclose(np.asarray(qos_j), per_user_np, atol=1e-5)


def _pairwise_f32(a):
    """The order ``user_sum`` documents, in NumPy float32."""
    a = np.asarray(a, np.float32)
    size = 1
    while size < a.shape[0]:
        size *= 2
    a = np.concatenate([a, np.zeros((size - a.shape[0],) + a.shape[1:],
                                    np.float32)])
    while a.shape[0] > 1:
        a = a[: a.shape[0] // 2] + a[a.shape[0] // 2:]
    return a[0]


@pytest.mark.parametrize("n", [0, 1, 2, 7, 64, 97])
def test_user_sum_has_one_fixed_order_at_any_batch_width(n):
    """Bit for bit the documented pairwise tree, alone and inside vmapped
    batches of any width: the order never depends on the batch."""
    import jax
    import jax.numpy as jnp

    from repro.core.scheduling import user_sum

    rng = np.random.default_rng(n)
    rows = rng.standard_normal((5, n, 3)).astype(np.float32) * 1e3
    want = np.stack([_pairwise_f32(r) for r in rows])
    for width in (1, 2, 5):
        got = np.asarray(jax.jit(jax.vmap(user_sum))(jnp.asarray(
            rows[:width])))
        np.testing.assert_array_equal(got, want[:width])
    np.testing.assert_allclose(want, rows.astype(np.float64).sum(axis=1),
                               rtol=1e-5, atol=1e-2)
