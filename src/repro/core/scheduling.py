"""Model scheduling: OMS (Algorithm 1) and the set-objective σ (Eq. 9/10).

Theorem 2: given a placement ``x``, the optimal schedule assigns each user
the placed implementation of its requested service with maximal QoS — the
maximum-spanning-tree of the auxiliary multigraph degenerates to a per-user
argmax because every user node hangs off the root independently.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .instance import PIESInstance, JaxInstance
from .qos import qos_matrix_np, eligibility_np

__all__ = [
    "oms_np",
    "sigma_np",
    "sigma_user_np",
    "schedule_value_np",
    "oms_jnp",
    "sigma_jnp",
    "user_sum",
]


def oms_np(
    inst: PIESInstance,
    x: np.ndarray,
    Q: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, float]:
    """Optimal Model Scheduling (Algorithm 1).

    Args:
      inst: the problem instance.
      x: [E, P] boolean placement decision.
      Q: optional precomputed QoS matrix (recomputed when omitted).

    Returns:
      ``(y, value)`` — ``y`` [U] int with the scheduled model index per user
      (−1 ⇒ request dropped to the central cloud), and the objective value
      Eq. (7) under this schedule.
    """
    if Q is None:
        Q = qos_matrix_np(inst)
    elig = eligibility_np(inst) & x[inst.u_edge]  # [U, P]
    masked = np.where(elig, Q, -1.0)
    y = masked.argmax(axis=1)
    served = masked[np.arange(inst.U), y] >= 0.0
    value = float(np.where(served, Q[np.arange(inst.U), y], 0.0).sum())
    y = np.where(served, y, -1)
    return y, value


def sigma_user_np(inst: PIESInstance, x: np.ndarray,
                  Q: Optional[np.ndarray] = None) -> np.ndarray:
    """Eq. (10): per-user optimal QoS σ_u(P) under placement ``x``."""
    if Q is None:
        Q = qos_matrix_np(inst)
    elig = eligibility_np(inst) & x[inst.u_edge]
    return np.where(elig, Q, 0.0).max(axis=1, initial=0.0)


def sigma_np(inst: PIESInstance, x: np.ndarray,
             Q: Optional[np.ndarray] = None) -> float:
    """Eq. (9): σ(P) = Σ_u σ_u(P) — objective value under optimal OMS."""
    return float(sigma_user_np(inst, x, Q).sum())


def schedule_value_np(inst: PIESInstance, y: np.ndarray,
                      Q: Optional[np.ndarray] = None) -> float:
    """Objective Eq. (7) of an explicit (possibly suboptimal) schedule."""
    if Q is None:
        Q = qos_matrix_np(inst)
    served = y >= 0
    return float(np.where(served, Q[np.arange(inst.U), np.maximum(y, 0)], 0.0).sum())


# ===========================================================================
# jnp twins
# ===========================================================================

def oms_jnp(Q, elig, u_edge, x):
    """jit-able OMS. ``Q``/``elig`` are [U, P]; ``x`` is [E, P] bool.

    Returns ``(y, per_user_qos)`` with ``y = −1`` for dropped requests.
    """
    import jax.numpy as jnp

    ok = elig & x[u_edge]
    masked = jnp.where(ok, Q, -1.0)
    y = jnp.argmax(masked, axis=1)
    best = jnp.take_along_axis(masked, y[:, None], axis=1)[:, 0]
    served = best >= 0.0
    qos = jnp.where(served, jnp.take_along_axis(Q, y[:, None], axis=1)[:, 0], 0.0)
    return jnp.where(served, y, -1), qos


def user_sum(a):
    """Σ over the leading (user) axis of ``a`` in one fixed order.

    A reduce leaves its order to the compiler, and on a TPU that order
    follows the layout chosen for the whole batch: the same instance then
    sums differently in a vmapped batch of 5 than in one of 2. This
    pairwise tree of elementwise adds is evaluated as written, so an
    item's value does not depend on the batch or device count it runs
    with (the sweep's resume, re-chunk and shard_map byte-identity)."""
    import jax.numpy as jnp

    n = a.shape[0]
    size = 1 << max(n - 1, 0).bit_length()
    if size != n:
        a = jnp.concatenate([a, jnp.zeros((size - n,) + a.shape[1:], a.dtype)])
    while a.shape[0] > 1:
        half = a.shape[0] // 2
        a = a[:half] + a[half:]
    return a[0]


def sigma_jnp(Q, elig, u_edge, x):
    """Eq. (9) as a jnp scalar."""
    import jax.numpy as jnp

    ok = elig & x[u_edge]
    return user_sum(jnp.where(ok, Q, 0.0).max(axis=1))
