"""Top-k sparse candidate sets for placement at scale.

The dense evaluator materializes the full QoS matrix ``Q [U, P]`` (and the
greedy loop's per-edge masked copies, ``[E, U, P]`` under ``vmap``) — fine
at the paper's 10²–10³ users, hopeless at 10⁶. But eligibility is sparse
by construction: user ``u`` can only ever be served by the implementations
of its requested service ``s_u``, of which there are at most ``M =
max_impls`` (≈ 10 in the paper's §VI-B setup). This module exploits that:

* :func:`impl_table_np` — the ``[S, M]`` service → implementation index
  table (−1 padded) that makes per-user candidate gathering O(1);
* :func:`topk_candidates_np` / :func:`topk_candidates_jnp` — the ``k``
  highest-QoS eligible implementations per user (``k = M`` keeps *every*
  eligible implementation, making the sparse path **exact**, not an
  approximation; ``k < M`` trades QoS for memory);
* :class:`CandidateSet` — the ``(cand_idx, cand_q) [U, k]`` pair
  representation consumed by
  :func:`repro.core.placement.egp_place_sparse_jax` and
  :func:`~repro.core.placement.sigma_sparse_jnp`.

Memory scales as ``U·k`` (+ ``E·P`` greedy state) instead of ``U×P×E`` —
the representation change behind the ``placement_scale`` benchmark.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from .instance import PIESInstance
from .qos import qos_matrix_np

__all__ = [
    "CandidateSet",
    "impl_table_np",
    "max_impls_of",
    "topk_candidates_np",
    "topk_candidates_jnp",
    "sigma_sparse_np",
]


@dataclasses.dataclass
class CandidateSet:
    """Sparse ``(user, candidate)`` pair representation of eligibility.

    ``cand_idx[u, c]`` is a model index into the instance's flattened
    ``(s, m)`` table, −1 for padding (user ``u`` has fewer than ``k``
    eligible implementations); ``cand_q[u, c]`` is the corresponding QoS
    (Eq. 1), 0 for padding. ``exact`` records whether the set kept every
    eligible implementation (``k ≥ M``), in which case sparse placement
    and scheduling reproduce the dense path's decisions.
    """

    cand_idx: np.ndarray  # [U, k] int64, −1 padded
    cand_q: np.ndarray    # [U, k] float64, 0 padded
    k: int
    exact: bool

    @property
    def U(self) -> int:
        return int(self.cand_idx.shape[0])


def max_impls_of(inst: PIESInstance) -> int:
    """``M`` — the largest implementation count over services."""
    if inst.P == 0:
        return 0
    return int(np.bincount(inst.sm_service, minlength=inst.S).max())


def impl_table_np(sm_service: np.ndarray,
                  n_services: Optional[int] = None) -> np.ndarray:
    """``[S, M]`` int64 table of model indices per service, −1 padded.

    Row ``s`` lists the flattened model indices implementing service ``s``
    in ascending index order — the gather target that turns per-user
    candidate enumeration into ``table[u_service]``.
    """
    sm_service = np.asarray(sm_service)
    P = sm_service.shape[0]
    S = int(n_services if n_services is not None
            else (sm_service.max() + 1 if P else 0))
    counts = np.bincount(sm_service, minlength=S)
    M = int(counts.max()) if P else 0
    table = np.full((S, M), -1, dtype=np.int64)
    order = np.argsort(sm_service, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(P) - np.repeat(starts, counts)
    table[sm_service[order], pos] = order
    return table


def topk_candidates_np(inst: PIESInstance, k: Optional[int] = None,
                       Q: Optional[np.ndarray] = None) -> CandidateSet:
    """NumPy reference top-k candidate selection (by QoS, ties → smaller
    model index, matching ``lax.top_k``'s first-occurrence order)."""
    if Q is None:
        Q = qos_matrix_np(inst)
    table = impl_table_np(inst.sm_service, inst.n_service_ids)
    M = table.shape[1]
    k_eff = M if k is None else min(int(k), M)
    cand = table[inst.u_service]                       # [U, M]
    valid = cand >= 0
    q = np.where(valid,
                 Q[np.arange(inst.U)[:, None], np.clip(cand, 0, None)],
                 -1.0)
    order = np.argsort(-q, axis=1, kind="stable")[:, :k_eff]
    idx = np.take_along_axis(cand, order, axis=1)
    vals = np.take_along_axis(q, order, axis=1)
    kept = vals >= 0.0                                  # drop −1 pad rows
    return CandidateSet(cand_idx=np.where(kept, idx, -1),
                        cand_q=np.where(kept, vals, 0.0),
                        k=k_eff, exact=k_eff >= M)


def topk_candidates_jnp(jinst, table, k: Optional[int] = None, *,
                        use_kernel: bool = False):
    """jit-able top-k candidates from a :class:`~repro.core.instance
    .JaxInstance` and a host-built :func:`impl_table_np`.

    Returns ``(cand_idx [U, k] int32, cand_q [U, k] float32)``. QoS per
    ``(user, candidate)`` pair is computed by the segmented kernel
    dispatcher (:func:`repro.kernels.qos_matrix.ops.qos_candidates` —
    Pallas on TPU / when ``use_kernel``, jnp reference otherwise); no
    ``[U, P]`` matrix is ever materialized.
    """
    import jax.numpy as jnp
    from jax import lax

    from repro.kernels.qos_matrix.ops import qos_candidates

    table = jnp.asarray(table, jnp.int32)
    M = int(table.shape[1])
    k_eff = M if k is None else min(int(k), M)
    cand = table[jinst.u_service]                      # [U, M]
    valid = cand >= 0
    safe = jnp.clip(table, 0, None)

    def per_pair(a):
        # a per-service [S, M] table, then one row per user: a gather with
        # [U, M] indices takes minutes to compile for a TPU at U = 10⁶
        return a[safe][jinst.u_service]

    q = qos_candidates(
        jinst.u_alpha, jinst.u_delta, jinst.u_share_k, jinst.u_share_w,
        per_pair(jinst.sm_acc), per_pair(jinst.sm_k), per_pair(jinst.sm_w),
        valid.astype(jnp.float32), delta_max=float(jinst.delta_max),
        use_kernel=use_kernel)
    q = jnp.where(valid, q, -1.0)                      # pad rows sort last
    if k_eff < M:
        vals, order = lax.top_k(q, k_eff)
        idx = jnp.take_along_axis(cand, order, axis=1)
    else:
        vals, idx = q, cand
    kept = vals >= 0.0
    return (jnp.where(kept, idx, -1).astype(jnp.int32),
            jnp.where(kept, vals, 0.0).astype(jnp.float32))


def sigma_sparse_np(inst: PIESInstance, x: np.ndarray,
                    cand: CandidateSet) -> float:
    """σ (Eq. 9) evaluated over the candidate pairs only.

    Exact when ``cand.exact`` (every eligible implementation present); a
    lower bound otherwise (a placed implementation outside the top-k is
    invisible to the sparse schedule).
    """
    valid = cand.cand_idx >= 0
    placed = np.zeros_like(valid)
    rows = np.broadcast_to(inst.u_edge[:, None], cand.cand_idx.shape)
    placed[valid] = x[rows[valid], cand.cand_idx[valid]]
    best = np.where(placed, cand.cand_q, 0.0).max(axis=1, initial=0.0)
    return float(best.sum())
