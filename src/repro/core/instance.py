"""PIES problem instances (§III of the paper).

An instance bundles the three entity families of the system model:

* edge clouds  ``e ∈ E`` with capacities ``K_e`` (communication), ``W_e``
  (computation), ``R_e`` (storage);
* service models ``(s, m) ∈ SM`` — flattened to ``P`` rows — with accuracy
  ``A_sm`` and costs ``k_sm`` (communication), ``w_sm`` (computation),
  ``r_sm`` (storage);
* user requests ``u ∈ U`` with covering edge ``e_u``, requested service
  ``s_u``, accuracy threshold ``α_u`` and delay threshold ``δ_u``.

Everything is stored as flat ``numpy`` arrays so the whole QoS model is
vectorizable; :meth:`PIESInstance.as_jax` mirrors the arrays into ``jnp``
for the jit-able implementations in :mod:`repro.core` and the Pallas
kernel in :mod:`repro.kernels.qos_matrix`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

__all__ = [
    "PIESInstance",
    "synthetic_instance",
    "realworld_instance",
    "REALWORLD_CATALOG",
    "tiny_instance",
    "draw_edge_capacities",
    "draw_service_catalog",
]


@dataclasses.dataclass
class PIESInstance:
    """A complete PIES problem instance (all arrays are host numpy)."""

    # --- edge clouds -----------------------------------------------------
    K: np.ndarray  # [E] communication capacity
    W: np.ndarray  # [E] computation capacity
    R: np.ndarray  # [E] storage capacity

    # --- service models (flattened (s, m) pairs) -------------------------
    sm_service: np.ndarray  # [P] int — service id of each model
    sm_acc: np.ndarray      # [P] A_sm ∈ [0, 1]
    sm_k: np.ndarray        # [P] communication cost
    sm_w: np.ndarray        # [P] computation cost
    sm_r: np.ndarray        # [P] storage cost

    # --- user requests ----------------------------------------------------
    u_edge: np.ndarray     # [U] int — covering edge cloud e_u
    u_service: np.ndarray  # [U] int — requested service s_u
    u_alpha: np.ndarray    # [U] accuracy threshold α_u ∈ [0, 1]
    u_delta: np.ndarray    # [U] delay threshold δ_u ∈ [0, δ_max]

    delta_max: float = 10.0

    # optional human-readable names (real-world catalog)
    model_names: Optional[Sequence[str]] = None

    # ---------------------------------------------------------------------
    @property
    def E(self) -> int:
        return int(self.K.shape[0])

    @property
    def P(self) -> int:
        return int(self.sm_service.shape[0])

    @property
    def U(self) -> int:
        return int(self.u_edge.shape[0])

    @property
    def S(self) -> int:
        return int(self.sm_service.max()) + 1 if self.P else 0

    @property
    def n_service_ids(self) -> int:
        """One more than the largest service id a user or an implementation
        names (:attr:`S` counts up to the last implemented service only)."""
        return max(self.S, int(self.u_service.max(initial=-1)) + 1)

    def covered_counts(self) -> np.ndarray:
        """``|U_e|`` for every edge cloud ``e`` (Eq. 5/6 sharing factor)."""
        return np.bincount(self.u_edge, minlength=self.E).astype(np.float64)

    def users_of_edge(self, e: int) -> np.ndarray:
        return np.nonzero(self.u_edge == e)[0]

    def models_of_service(self, s: int) -> np.ndarray:
        return np.nonzero(self.sm_service == s)[0]

    def validate(self) -> None:
        assert self.u_edge.min(initial=0) >= 0 and (
            self.U == 0 or self.u_edge.max() < self.E
        )
        assert np.all(self.sm_acc >= 0.0) and np.all(self.sm_acc <= 1.0)
        assert np.all(self.u_alpha >= 0.0) and np.all(self.u_alpha <= 1.0)
        assert np.all(self.u_delta >= 0.0) and np.all(
            self.u_delta <= self.delta_max + 1e-9
        )
        assert np.all(self.sm_r > 0), "storage costs must be positive"
        # every service has ≥ 1 implementation (paper assumption m_s ≥ 1)
        if self.U:
            req = np.unique(self.u_service)
            have = np.unique(self.sm_service)
            assert np.all(np.isin(req, have)), "user requests unknown service"

    def as_jax(self, n_slots: Optional[int] = None):
        """Return a :class:`JaxInstance` pytree mirror of this instance.

        With ``n_slots``, the users are padded to that many slots. A padded
        slot sits at edge ``E``, past every real edge, with α = δ = 0 and
        service :attr:`n_service_ids`, which no implementation implements:
        given an implementation table with that row, it has no candidate
        pair and is counted at no edge.
        """
        import jax.numpy as jnp

        counts = self.covered_counts()
        users = [self.u_alpha, self.u_delta, self.u_service, self.u_edge,
                 counts[self.u_edge] / self.K[self.u_edge],
                 counts[self.u_edge] / self.W[self.u_edge]]
        if n_slots is not None:
            if n_slots < self.U:
                raise ValueError(f"{n_slots} user slots cannot hold "
                                 f"{self.U} users")
            fills = (0.0, 0.0, self.n_service_ids, self.E, 0.0, 0.0)
            users = [np.concatenate([a, np.full(n_slots - self.U, f,
                                                np.asarray(a).dtype)])
                     for a, f in zip(users, fills)]
        alpha, delta, service, edge, share_k, share_w = users
        return JaxInstance(
            u_alpha=jnp.asarray(alpha, jnp.float32),
            u_delta=jnp.asarray(delta, jnp.float32),
            u_service=jnp.asarray(service, jnp.int32),
            u_edge=jnp.asarray(edge, jnp.int32),
            u_share_k=jnp.asarray(share_k, jnp.float32),
            u_share_w=jnp.asarray(share_w, jnp.float32),
            sm_service=jnp.asarray(self.sm_service, jnp.int32),
            sm_acc=jnp.asarray(self.sm_acc, jnp.float32),
            sm_k=jnp.asarray(self.sm_k, jnp.float32),
            sm_w=jnp.asarray(self.sm_w, jnp.float32),
            sm_r=jnp.asarray(self.sm_r, jnp.float32),
            R=jnp.asarray(self.R, jnp.float32),
            delta_max=jnp.float32(self.delta_max),
        )


@dataclasses.dataclass
class JaxInstance:
    """jnp mirror of :class:`PIESInstance` with the per-user sharing factors
    ``|U_e|/K_e`` and ``|U_e|/W_e`` pre-gathered (Eq. 5/6)."""

    u_alpha: "object"
    u_delta: "object"
    u_service: "object"
    u_edge: "object"
    u_share_k: "object"  # [U] = |U_{e_u}| / K_{e_u}
    u_share_w: "object"  # [U] = |U_{e_u}| / W_{e_u}
    sm_service: "object"
    sm_acc: "object"
    sm_k: "object"
    sm_w: "object"
    sm_r: "object"
    R: "object"
    delta_max: "object"


def _register_jax_instance():  # pragma: no cover - import-time plumbing
    try:
        import jax
    except Exception:
        return
    fields = [f.name for f in dataclasses.fields(JaxInstance)]
    jax.tree_util.register_pytree_node(
        JaxInstance,
        lambda x: ([getattr(x, f) for f in fields], None),
        lambda _, leaves: JaxInstance(**dict(zip(fields, leaves))),
    )


_register_jax_instance()


# ===========================================================================
# Instance generators
# ===========================================================================

def draw_edge_capacities(rng: np.random.Generator, n_edges: int):
    """§VI-B edge-cloud draws (the single source of the paper's ranges):
    ``K_e, W_e ~ U{300..600}``, ``R_e ~ U{100..200}``. Returns (K, W, R)."""
    K = rng.integers(300, 601, size=n_edges).astype(np.float64)
    W = rng.integers(300, 601, size=n_edges).astype(np.float64)
    R = rng.integers(100, 201, size=n_edges).astype(np.float64)
    return K, W, R


def draw_service_catalog(rng: np.random.Generator, n_services: int,
                         max_impls: int):
    """§VI-B service-model draws: ``U{1..max_impls}`` implementations per
    service, ``k, w ~ U{15..30}``, ``r ~ U{10..20}``,
    ``A ~ clip(N(0.65, 0.1), 0, 1)``.

    Returns ``(sm_service, sm_acc, sm_k, sm_w, sm_r)``.
    """
    impls = rng.integers(1, max_impls + 1, size=n_services)
    sm_service = np.repeat(np.arange(n_services), impls)
    P = sm_service.shape[0]
    sm_k = rng.integers(15, 31, size=P).astype(np.float64)
    sm_w = rng.integers(15, 31, size=P).astype(np.float64)
    sm_r = rng.integers(10, 21, size=P).astype(np.float64)
    sm_acc = np.clip(rng.normal(0.65, 0.1, size=P), 0.0, 1.0)
    return sm_service, sm_acc, sm_k, sm_w, sm_r


def synthetic_instance(
    n_users: int,
    n_edges: int = 10,
    n_services: int = 100,
    max_impls: int = 10,
    delta_max: float = 10.0,
    seed: int = 0,
    alpha_scale: float = 0.125,
    delta_scale: float = 1.5,
) -> PIESInstance:
    """Numerical-simulation setup of §VI-B, parameter-for-parameter.

    ``K_e, W_e ~ U{300..600}``, ``R_e ~ U{100..200}``; per service model
    ``k, w ~ U{15..30}``, ``r ~ U{10..20}``, ``A ~ clip(N(0.65, 0.1), 0, 1)``;
    each service has ``U{1..max_impls}`` implementations; user services are
    uniform; ``α_u = 1 − ε`` with ``ε ~ clip(Exp(scale=0.125), 0, 1)``;
    ``δ_u ~ clip(Exp(scale=1.5), 0, δ_max)`` with ``δ_max = 10``.

    The paper writes the exponential parameters as rates ``λ``; we follow
    the conventional NumPy ``scale`` reading (``scale = 0.125`` ⇒ strict
    accuracy thresholds near 1), which reproduces the paper's reported
    approximation-ratio regime (see EXPERIMENTS.md §Paper-validation).
    """
    rng = np.random.default_rng(seed)
    K, W, R = draw_edge_capacities(rng, n_edges)
    sm_service, sm_acc, sm_k, sm_w, sm_r = draw_service_catalog(
        rng, n_services, max_impls)

    u_edge = rng.integers(0, n_edges, size=n_users)
    u_service = rng.integers(0, n_services, size=n_users)
    u_alpha = 1.0 - np.clip(rng.exponential(alpha_scale, size=n_users), 0.0, 1.0)
    u_delta = np.clip(rng.exponential(delta_scale, size=n_users), 0.0, delta_max)

    inst = PIESInstance(
        K=K, W=W, R=R,
        sm_service=sm_service, sm_acc=sm_acc, sm_k=sm_k, sm_w=sm_w, sm_r=sm_r,
        u_edge=u_edge, u_service=u_service, u_alpha=u_alpha, u_delta=u_delta,
        delta_max=delta_max,
    )
    inst.validate()
    return inst


#: Table I of the paper: (name, accuracy A_sm, avg. computation delay sec).
REALWORLD_CATALOG = [
    ("AlexNet", 0.5652, 0.04),
    ("DenseNet", 0.7714, 0.47),
    ("GoogLeNet", 0.6978, 0.13),
    ("MobileNet", 0.7188, 0.06),
    ("ResNet", 0.6976, 0.08),
    ("SqueezeNet", 0.5809, 0.07),
]


def realworld_instance(
    n_users: int = 300,
    seed: int = 0,
    tran_delay: float = 0.05,
    comp_contention: float = 2.0,
    delta_max: float = 1.0,
) -> PIESInstance:
    """Real-world setup of §VI-C: one edge cloud (the iMac), one image-
    classification service with the six Table-I implementations, 300
    requests (3 IoT devices × 100 images).

    ``R_e = 1`` and ``r_sm = 1`` (single placement slot), ``k_sm = 1``.
    ``α_u = 1 − ε``, ``ε ~ clip(Exp(scale=0.0625), 0, 1)``;
    ``δ_u ~ clip(N(0.5, 0.125), 0, 1)``, ``δ_max = 1`` second.

    ``K_e``/``W_e`` are "robustly tuned to match the real-world computation
    and communication delay" (paper §VI-C): we pick ``W_e = |U_e| /
    comp_contention`` so a model's effective computation delay is its
    measured Table-I delay times the contention factor, and ``K_e = |U_e| ·
    k_sm / tran_delay`` so transmission costs ``tran_delay`` seconds.
    """
    rng = np.random.default_rng(seed)
    names = [n for n, _, _ in REALWORLD_CATALOG]
    acc = np.array([a for _, a, _ in REALWORLD_CATALOG])
    comp = np.array([c for _, _, c in REALWORLD_CATALOG])

    P = len(names)
    K = np.array([n_users * 1.0 / tran_delay])
    W = np.array([n_users / comp_contention])
    R = np.array([1.0])

    inst = PIESInstance(
        K=K, W=W, R=R,
        sm_service=np.zeros(P, dtype=np.int64),
        sm_acc=acc,
        sm_k=np.ones(P),
        sm_w=comp,  # D_comp = w · |U_e| / W_e = comp · contention
        sm_r=np.ones(P),
        u_edge=np.zeros(n_users, dtype=np.int64),
        u_service=np.zeros(n_users, dtype=np.int64),
        u_alpha=1.0 - np.clip(rng.exponential(0.0625, size=n_users), 0.0, 1.0),
        u_delta=np.clip(rng.normal(0.5, 0.125, size=n_users), 0.0, delta_max),
        delta_max=delta_max,
        model_names=names,
    )
    inst.validate()
    return inst


def tiny_instance(seed: int = 0, n_users: int = 12, n_edges: int = 2,
                  n_services: int = 4, max_impls: int = 3) -> PIESInstance:
    """A brute-forceable instance for exactness tests."""
    return synthetic_instance(
        n_users=n_users, n_edges=n_edges, n_services=n_services,
        max_impls=max_impls, seed=seed,
    )
