"""Jit'd dispatcher: Pallas on TPU, interpret-mode kernel or jnp elsewhere."""
from __future__ import annotations

import functools

import jax

from repro.obs import named_scope

from .qos_matrix import (greedy_argmax_pallas, qos_candidates_pallas,
                         qos_matrix_pallas)
from .ref import greedy_argmax_ref, qos_candidates_ref, qos_matrix_ref


@functools.partial(jax.jit, static_argnames=("delta_max", "use_kernel"))
def qos_matrix(u_alpha, u_delta, u_share_k, u_share_w, u_service,
               sm_acc, sm_k, sm_w, sm_service, *, delta_max: float,
               use_kernel: bool = True):
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel:
        with named_scope("qos_matrix_pallas"):
            return qos_matrix_pallas(
                u_alpha, u_delta, u_share_k, u_share_w, u_service,
                sm_acc, sm_k, sm_w, sm_service, delta_max=delta_max,
                interpret=not on_tpu)
    with named_scope("qos_matrix_ref"):
        return qos_matrix_ref(
            u_alpha, u_delta, u_share_k, u_share_w, u_service,
            sm_acc, sm_k, sm_w, sm_service, delta_max=delta_max)


@functools.partial(jax.jit, static_argnames=("delta_max", "use_kernel"))
def qos_candidates(u_alpha, u_delta, u_share_k, u_share_w,
                   cand_acc, cand_k, cand_w, cand_valid, *,
                   delta_max: float, use_kernel: bool = True):
    """Segmented QoS over pre-gathered ``(user, candidate)`` pairs [U, K]."""
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel:
        with named_scope("qos_candidates_pallas"):
            return qos_candidates_pallas(
                u_alpha, u_delta, u_share_k, u_share_w,
                cand_acc, cand_k, cand_w, cand_valid,
                delta_max=delta_max, interpret=not on_tpu)
    with named_scope("qos_candidates_ref"):
        return qos_candidates_ref(
            u_alpha, u_delta, u_share_k, u_share_w,
            cand_acc, cand_k, cand_w, cand_valid, delta_max=delta_max)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def greedy_argmax(v, mask, *, use_kernel: bool = True):
    """Masked per-edge argmax over the benefit map (Alg. 3 line 11)."""
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel:
        with named_scope("greedy_argmax_pallas"):
            return greedy_argmax_pallas(v, mask, interpret=not on_tpu)
    with named_scope("greedy_argmax_ref"):
        return greedy_argmax_ref(v, mask)


def qos_matrix_from_instance(jinst, use_kernel: bool = True):
    """Convenience wrapper over a repro.core JaxInstance."""
    from .qos_matrix import check_service_ids

    check_service_ids(jinst.u_service, jinst.sm_service)
    return qos_matrix(
        jinst.u_alpha, jinst.u_delta, jinst.u_share_k, jinst.u_share_w,
        jinst.u_service, jinst.sm_acc, jinst.sm_k, jinst.sm_w,
        jinst.sm_service, delta_max=float(jinst.delta_max),
        use_kernel=use_kernel)


def qos_candidates_from_instance(jinst, table, k=None, *,
                                 use_kernel: bool = True):
    """Top-k candidate build (gather + segmented QoS kernel + top-k) from a
    JaxInstance and a host-built impl table; returns ``(cand_idx, cand_q)``.
    """
    from repro.core.candidates import topk_candidates_jnp

    return topk_candidates_jnp(jinst, table, k, use_kernel=use_kernel)
