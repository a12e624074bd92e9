"""Batched accelerator-side scenario evaluation.

Monte-Carlo sweeps over (scenario × seed × tick) evaluate hundreds of
independent :class:`PIESInstance`\\ s. Doing that with a Python loop pays a
dispatch + trace per instance; instead, :func:`pad_instances` pads every
instance to the batch's fixed (U, P, E) envelope and stacks them into a
single batched :class:`~repro.core.instance.JaxInstance` pytree, and
:func:`evaluate_batch` runs QoS-matrix construction, greedy placement
(:func:`egp_place_jax` / :func:`agp_place_jax`) and the σ objective for the
*whole stack* inside one ``jax.jit``'d ``vmap`` — one accelerator call per
sweep.

Padding conventions (chosen so padded rows are provably inert):

* **users** — padded slots request the dummy service id ``S`` that no model
  implements (eligibility row ≡ False ⇒ zero QoS, zero greedy gain, zero σ)
  and are covered by a padded edge, so they never enter a real edge's user
  mask or satisfaction test;
* **models** — padded rows carry the distinct dummy service ``S + 1`` (no
  user requests it) and an effectively-infinite storage cost, so they are
  never feasible;
* **edges** — padded edges have zero storage, so the greedy loops exit
  immediately; at least one padded edge always exists to host padded users.

``evaluate_host`` is the NumPy reference path (per-instance
``egp_np``/``agp_np`` + ``sigma_np``) the batched results are validated
against — see ``tests/test_workloads.py`` and ``benchmarks/scenarios.py``.

Two scale paths sit on top of the global-pad evaluator:

* **Bucketed batching** (:func:`bucket_instances` / :class:`BucketedBatch`)
  — instances are grouped into geometric (power-of-two) ``(U, P, E)`` size
  classes and each bucket is padded to its *own* envelope, so one outlier
  no longer inflates every instance's pad. The bucket envelope is a pure
  function of each instance's own dims (never of its batch neighbours),
  which keeps per-item results independent of batch composition — the
  property sweep resume/fleet-merge byte-identity rests on.
  :func:`evaluate_batch` accepts either batch type; bucket pad waste is
  reported on the ``placement.bucket_pad_waste`` obs gauge.
* **Sparse top-k candidates** (:func:`evaluate_sparse`) — skips the dense
  ``[U, P]`` QoS matrix entirely: per-user top-k candidate pairs
  (:mod:`repro.core.candidates`) feed
  :func:`repro.core.placement.egp_place_sparse_jax`, with memory O(U·k)
  instead of O(U·P·E). Exact vs the host path when ``k`` keeps every
  eligible implementation (the default).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.instance import JaxInstance, PIESInstance
from repro.core.placement import agp_np, egp_np
from repro.core.qos import qos_matrix_np
from repro.core.scheduling import sigma_np

__all__ = [
    "PaddedBatch",
    "BucketedBatch",
    "pad_instances",
    "bucket_envelope",
    "bucket_indices",
    "bucket_instances",
    "single_evaluator",
    "evaluate_batch",
    "evaluate_sparse",
    "sparse_evaluator",
    "evaluate_host",
    "sweep",
]

#: Storage cost assigned to padded model rows — larger than any edge budget.
_PAD_STORAGE = 1e9


@dataclasses.dataclass
class PaddedBatch:
    """A stack of instances padded to a common (U, P, E) envelope."""

    jax_instance: JaxInstance      # every leaf is batched: [B, ...]
    n_services: int                # static scatter width (incl. dummy ids)
    dims: List[Tuple[int, int, int]]   # true (U, P, E) per instance

    @property
    def B(self) -> int:
        return len(self.dims)


@dataclasses.dataclass
class BucketedBatch:
    """Instances grouped into per-size-class :class:`PaddedBatch`\\ es.

    ``index[b]`` maps bucket ``b``'s rows back to positions in the original
    instance sequence; ``envelopes[b]`` is the bucket's ``(U_pad, P_pad,
    E_pad)``. Buckets are ordered by envelope (deterministic regardless of
    input order).
    """

    buckets: List[PaddedBatch]
    index: List[np.ndarray]
    envelopes: List[Tuple[int, int, int]]
    dims: List[Tuple[int, int, int]]   # true (U, P, E) in original order

    @property
    def B(self) -> int:
        return len(self.dims)

    @property
    def pad_waste(self) -> float:
        """Fraction of evaluated (U·P·E) cells that are padding, in [0, 1).

        The quantity the bucketing exists to shrink: under a single global
        envelope every instance pays the max instance's cell count."""
        true = sum(u * p * (e + 1) for u, p, e in self.dims)
        padded = sum(len(idx) * up * pp * ep
                     for idx, (up, pp, ep) in zip(self.index, self.envelopes))
        return 1.0 - true / padded if padded else 0.0


def _pow2_ceil(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def bucket_envelope(U: int, P: int, E: int,
                    cap: Optional[Tuple[int, int, int]] = None
                    ) -> Tuple[int, int, int]:
    """Geometric (power-of-two) size class of one instance's dims.

    Pure function of ``(U, P, E)`` (and the static ``cap``, e.g. a sweep
    group's :func:`repro.sweeps.spec.envelope_for` envelope) — deliberately
    *not* of any batch neighbour, so an item's evaluated envelope is
    identical however the sweep is chunked, resumed, or fleet-split. The
    edge axis buckets ``E + 1`` (a padded host edge always exists).
    """
    env = (_pow2_ceil(U), _pow2_ceil(P), _pow2_ceil(E + 1))
    if cap is not None:
        env = tuple(min(a, int(c)) for a, c in zip(env, cap))
    assert env[0] >= U and env[1] >= P and env[2] > E, \
        f"cap {cap} below instance dims ({U},{P},{E})"
    return env


def bucket_indices(instances: Sequence[PIESInstance],
                   cap: Optional[Tuple[int, int, int]] = None
                   ) -> List[Tuple[Tuple[int, int, int], List[int]]]:
    """Group instance positions by :func:`bucket_envelope`, sorted by
    envelope; within a bucket, original order is preserved."""
    groups: Dict[Tuple[int, int, int], List[int]] = {}
    for i, inst in enumerate(instances):
        groups.setdefault(bucket_envelope(inst.U, inst.P, inst.E, cap),
                          []).append(i)
    return sorted(groups.items())


def bucket_instances(instances: Sequence[PIESInstance],
                     cap: Optional[Tuple[int, int, int]] = None
                     ) -> BucketedBatch:
    """Stack ``instances`` into one :class:`PaddedBatch` per size bucket."""
    assert instances, "cannot bucket an empty batch"
    buckets, index, envelopes = [], [], []
    for env, idx in bucket_indices(instances, cap):
        buckets.append(pad_instances([instances[i] for i in idx], *env))
        index.append(np.asarray(idx))
        envelopes.append(env)
    return BucketedBatch(buckets=buckets, index=index, envelopes=envelopes,
                         dims=[(i.U, i.P, i.E) for i in instances])


def _share_factors(inst: PIESInstance) -> Tuple[np.ndarray, np.ndarray]:
    counts = inst.covered_counts()
    return (counts[inst.u_edge] / inst.K[inst.u_edge],
            counts[inst.u_edge] / inst.W[inst.u_edge])


def pad_instances(instances: Sequence[PIESInstance],
                  u_pad: Optional[int] = None,
                  p_pad: Optional[int] = None,
                  e_pad: Optional[int] = None) -> PaddedBatch:
    """Stack ``instances`` into one batched, fixed-shape JaxInstance."""
    import jax.numpy as jnp

    assert instances, "cannot pad an empty batch"
    U_pad = u_pad or max(i.U for i in instances)
    P_pad = p_pad or max(i.P for i in instances)
    # +1 guarantees a padded edge exists in every instance (hosts pad users)
    E_pad = e_pad or (max(i.E for i in instances) + 1)
    S_max = max(int(i.sm_service.max()) + 1 if i.P else 0 for i in instances)
    user_dummy, model_dummy = S_max, S_max + 1

    rows: Dict[str, List[np.ndarray]] = {f.name: [] for f in
                                         dataclasses.fields(JaxInstance)}
    dims = []
    for inst in instances:
        U, P, E = inst.U, inst.P, inst.E
        assert U <= U_pad and P <= P_pad and E < E_pad, \
            f"instance ({U},{P},{E}) exceeds pad envelope " \
            f"({U_pad},{P_pad},{E_pad})"
        dims.append((U, P, E))
        du, dp, de = U_pad - U, P_pad - P, E_pad - E
        share_k, share_w = _share_factors(inst)

        def upad(a, fill):
            return np.concatenate([np.asarray(a, np.float64),
                                   np.full(du, fill)])

        def ppad(a, fill):
            return np.concatenate([np.asarray(a, np.float64),
                                   np.full(dp, fill)])

        rows["u_alpha"].append(upad(inst.u_alpha, 0.0))
        rows["u_delta"].append(upad(inst.u_delta, 0.0))
        rows["u_share_k"].append(upad(share_k, 0.0))
        rows["u_share_w"].append(upad(share_w, 0.0))
        rows["u_service"].append(np.concatenate(
            [inst.u_service, np.full(du, user_dummy, dtype=np.int64)]))
        rows["u_edge"].append(np.concatenate(
            [inst.u_edge, np.full(du, E_pad - 1, dtype=np.int64)]))
        rows["sm_service"].append(np.concatenate(
            [inst.sm_service, np.full(dp, model_dummy, dtype=np.int64)]))
        rows["sm_acc"].append(ppad(inst.sm_acc, 0.0))
        rows["sm_k"].append(ppad(inst.sm_k, 0.0))
        rows["sm_w"].append(ppad(inst.sm_w, 0.0))
        rows["sm_r"].append(ppad(inst.sm_r, _PAD_STORAGE))
        rows["R"].append(np.concatenate([inst.R, np.zeros(de)]))
        rows["delta_max"].append(np.float64(inst.delta_max))

    int_fields = {"u_service", "u_edge", "sm_service"}
    leaves = {
        name: jnp.asarray(np.stack(vals),
                          jnp.int32 if name in int_fields else jnp.float32)
        for name, vals in rows.items()
    }
    return PaddedBatch(jax_instance=JaxInstance(**leaves),
                       n_services=model_dummy + 1, dims=dims)


def single_evaluator(algo: str, n_services: int, max_iters: int):
    """The per-instance evaluator ``JaxInstance -> (value, x)`` — the unit
    that :func:`evaluate_batch` vmaps and :mod:`repro.sweeps.shard` wraps in
    ``shard_map(vmap(...))`` across mesh batch axes."""
    from repro.core.placement import agp_place_jax, egp_place_jax
    from repro.core.qos import eligibility_jnp, qos_matrix_jnp
    from repro.core.scheduling import sigma_jnp

    def one(inst: JaxInstance):
        Q = qos_matrix_jnp(inst)
        elig = eligibility_jnp(inst)
        if algo == "egp":
            x = egp_place_jax(Q, elig, inst.u_edge, inst.u_service,
                              inst.sm_service, inst.sm_r, inst.R,
                              n_services, max_iters=max_iters)
        elif algo == "agp":
            x = agp_place_jax(Q, elig, inst.u_edge, inst.sm_r, inst.R,
                              max_iters=max_iters)
        else:
            raise ValueError(f"unknown batched algorithm {algo!r}")
        value = sigma_jnp(Q, elig, inst.u_edge, x)
        return value, x

    return one


def _build_evaluator(algo: str, n_services: int, max_iters: int):
    import jax

    return jax.jit(jax.vmap(single_evaluator(algo, n_services, max_iters)))


@functools.lru_cache(maxsize=16)
def _cached_evaluator(algo: str, n_services: int, max_iters: int):
    return _build_evaluator(algo, n_services, max_iters)


def evaluate_batch(batch, algo: str = "egp", max_iters: int = 512):
    """Batched placement evaluation: ``(values [B], x)``.

    For a :class:`PaddedBatch` this is one jitted accelerator call and
    ``x`` is ``[B, E_pad, P_pad]``. For a :class:`BucketedBatch` each
    bucket runs through the same jitted evaluator at its own envelope
    (one call per size class) and results are re-assembled in original
    instance order — ``values`` is a float64 NumPy array and ``x`` a list
    of per-instance ``[E_pad_b, P_pad_b]`` placements (envelopes differ
    across buckets). Pad waste is published on the
    ``placement.bucket_pad_waste`` gauge.

    ``values[b]`` is σ(EGP/AGP placement) of instance ``b``; padding
    contributes exactly zero (see module docstring), so values match the
    per-instance host path up to float32 accumulation.
    """
    if isinstance(batch, BucketedBatch):
        from repro import obs

        values = np.empty(batch.B, dtype=np.float64)
        xs: List = [None] * batch.B
        for pb, idx in zip(batch.buckets, batch.index):
            v, x = evaluate_batch(pb, algo=algo, max_iters=max_iters)
            values[idx] = np.asarray(v, np.float64)
            for j, i in enumerate(idx):
                xs[int(i)] = x[j]
        tracer = obs.get_tracer()
        if tracer is not None:
            tracer.metrics.gauge("placement.bucket_pad_waste").set(
                batch.pad_waste)
        return values, xs
    fn = _cached_evaluator(algo, batch.n_services, max_iters)
    values, x = fn(batch.jax_instance)
    return values, x


@functools.lru_cache(maxsize=16)
def sparse_evaluator(max_iters: int, use_kernel: bool):
    """The jitted sparse EGP tick over top-k candidate pairs:
    ``(cand_idx, cand_q, u_edge, sm_service, sm_r, R) -> (σ, x, n_iters,
    n_rescores)``, the last two the greedy loop's counts (int32 scalars:
    iterations, and iterations that ran the re-score)."""
    import jax

    from repro.core.placement import _egp_place_sparse, sigma_sparse_jnp

    def run(cand_idx, cand_q, u_edge, sm_service, sm_r, R):
        x, info = _egp_place_sparse(
            cand_idx, cand_q, u_edge, sm_service, sm_r, R,
            max_iters=max_iters, use_kernel=use_kernel, with_trace=False)
        return (sigma_sparse_jnp(cand_idx, cand_q, u_edge, x), x,
                info["n_iters"], info["n_rescores"])

    return jax.jit(run)


def evaluate_sparse(instances: Sequence[PIESInstance], algo: str = "egp",
                    k: Optional[int] = None, max_iters: Optional[int] = None,
                    use_kernel: bool = False):
    """Top-k sparse placement per instance: ``(values [B], x list)``.

    The scale path: no ``[U, P]`` QoS matrix — per-user candidate pairs
    (``k`` defaults to *all* eligible implementations, making the result
    exact vs :func:`evaluate_host`; smaller ``k`` is the documented
    approximation) drive the lock-step sparse EGP loop.
    ``max_iters=None`` uses ``P + 1`` (an edge never picks more than P
    models, so the greedy runs to its natural stop). ``use_kernel`` routes
    segmented QoS and the per-edge argmax through the Pallas kernels.

    With a tracer enabled each instance's host phases are spans:
    ``placement.as_jax`` (host arrays to the device), ``placement
    .candidates`` (the implementation table and the candidate build, which
    JAX dispatches op by op), ``placement.greedy`` (dispatch of the jitted
    greedy and σ) and ``placement.wait`` (the host blocks on σ). The
    greedy's counts add to the ``placement.greedy_iters`` and
    ``placement.greedy_rescores`` counters, and the effective ``k`` is
    published on the ``placement.candidate_k`` gauge. With tracing off
    nothing reads the counts.
    """
    if algo != "egp":
        raise ValueError(f"sparse path implements 'egp' only, got {algo!r}")
    from repro import obs
    from repro.core.candidates import impl_table_np
    from repro.kernels.qos_matrix.ops import qos_candidates_from_instance

    values, xs = [], []
    tracer = obs.get_tracer()
    for inst in instances:
        with obs.span("placement.as_jax"):
            ji = inst.as_jax()
        with obs.span("placement.candidates"):
            table = impl_table_np(inst.sm_service, inst.S)
            cand_idx, cand_q = qos_candidates_from_instance(
                ji, table, k, use_kernel=use_kernel)
        if tracer is not None:
            tracer.metrics.gauge("placement.candidate_k").set(
                int(cand_idx.shape[1]))
        mi = int(max_iters) if max_iters is not None else inst.P + 1
        with obs.span("placement.greedy"):
            v, x, n_iters, n_rescores = sparse_evaluator(mi, use_kernel)(
                cand_idx, cand_q, ji.u_edge, ji.sm_service, ji.sm_r, ji.R)
        with obs.span("placement.wait"):
            values.append(float(v))
        if tracer is not None:
            tracer.count("placement.greedy_iters", int(n_iters))
            tracer.count("placement.greedy_rescores", int(n_rescores))
        xs.append(x)
    return np.asarray(values, np.float64), xs


def evaluate_host(instances: Sequence[PIESInstance],
                  algo: str = "egp") -> np.ndarray:
    """NumPy reference: per-instance greedy placement + σ, no batching."""
    place = {"egp": egp_np, "agp": agp_np}[algo]
    out = []
    for inst in instances:
        Q = qos_matrix_np(inst)
        out.append(sigma_np(inst, place(inst, Q), Q))
    return np.asarray(out)


def sweep(scenario_names: Sequence[str], seeds: Sequence[int],
          n_ticks: Optional[int] = None, algo: str = "egp",
          **overrides) -> Dict:
    """Monte-Carlo sweep: every (scenario, seed, tick) instance evaluated
    in a single jitted call.

    Returns ``{"values": {name: [n_seeds, n_ticks] np.ndarray},
    "instances": [...], "labels": [(name, seed, tick)], "batch": batch}``.
    """
    from .scenarios import get_scenario

    instances: List[PIESInstance] = []
    labels: List[Tuple[str, int, int]] = []
    ticks_of: Dict[str, int] = {}
    for name in scenario_names:
        scenario = get_scenario(name, **overrides)
        T = int(n_ticks or scenario.n_ticks)
        ticks_of[name] = T
        for seed in seeds:
            for tick, inst in enumerate(scenario.horizon(seed, T)):
                instances.append(inst)
                labels.append((name, int(seed), tick))

    batch = bucket_instances(instances)
    values, _ = evaluate_batch(batch, algo=algo)
    values = np.asarray(values, np.float64)

    shaped: Dict[str, np.ndarray] = {}
    off = 0
    for name in scenario_names:
        T = ticks_of[name]
        n = len(seeds) * T
        shaped[name] = values[off:off + n].reshape(len(seeds), T)
        off += n
    return {"values": shaped, "instances": instances, "labels": labels,
            "batch": batch}
