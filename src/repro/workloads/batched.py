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
* **The biased placer** (:func:`place_on_device`) — the same sparse greedy
  with :class:`repro.core.dynamic.DynamicPlacer`'s hysteresis, its users
  padded to a size class (:func:`user_size_class`) so that a tick's user
  count compiles nothing new, and its resident set kept on the device.
"""
from __future__ import annotations

import dataclasses
import functools
import math
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
    "user_size_class",
    "placer_program",
    "place_on_device",
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
    n_rescores, n_group_users)``, the last three the greedy loop's counts
    (int32 scalars: iterations, iterations that ran the re-score, and the
    users those re-scores visited)."""
    import jax

    from repro.core.placement import _egp_place_sparse, sigma_sparse_jnp

    def run(cand_idx, cand_q, u_edge, sm_service, sm_r, R):
        x, info = _egp_place_sparse(
            cand_idx, cand_q, u_edge, sm_service, sm_r, R,
            max_iters=max_iters, use_kernel=use_kernel, with_trace=False)
        return (sigma_sparse_jnp(cand_idx, cand_q, u_edge, x), x,
                info["n_iters"], info["n_rescores"], info["n_group_users"])

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
    greedy's counts add to the ``placement.greedy_iters``,
    ``placement.greedy_rescores`` and ``placement.greedy_group_users``
    (users the re-scores visited) counters, and the effective ``k`` is
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
            table = impl_table_np(inst.sm_service, inst.n_service_ids)
            cand_idx, cand_q = qos_candidates_from_instance(
                ji, table, k, use_kernel=use_kernel)
        if tracer is not None:
            tracer.metrics.gauge("placement.candidate_k").set(
                int(cand_idx.shape[1]))
        mi = int(max_iters) if max_iters is not None else inst.P + 1
        with obs.span("placement.greedy"):
            v, x, n_iters, n_rescores, n_group = sparse_evaluator(
                mi, use_kernel)(
                cand_idx, cand_q, ji.u_edge, ji.sm_service, ji.sm_r, ji.R)
        with obs.span("placement.wait"):
            values.append(float(v))
        if tracer is not None:
            tracer.count("placement.greedy_iters", int(n_iters))
            tracer.count("placement.greedy_rescores", int(n_rescores))
            tracer.count("placement.greedy_group_users", int(n_group))
        xs.append(x)
    return np.asarray(values, np.float64), xs


#: The largest user size class of :func:`user_size_class`; the classes
#: below it shrink by √2 a step.
USER_CLASS_TOP = 1_000_000


def _user_class(j: int) -> int:
    return math.ceil(USER_CLASS_TOP * 2.0 ** (-j / 2))


def user_size_class(n: int) -> int:
    """The user slots of a placer tick of ``n`` users: the least
    ⌈10⁶ · 2^(−j/2)⌉, j a whole number, that holds ``n``. Geometric with
    ratio √2, so a tick pads by at most 41 % and seven classes span
    10⁵–10⁶; anchored at 10⁶, so that a 10⁶-user tick pads nothing. A pure
    function of ``n``, so every tick of one size compiles the same
    programs."""
    if n <= 1:
        return max(int(n), 0)
    j = math.floor(2 * math.log2(USER_CLASS_TOP / n))
    while _user_class(j) < n:
        j -= 1
    while _user_class(j + 1) >= n:
        j += 1
    return _user_class(j)


@functools.lru_cache(maxsize=16)
def placer_program(max_iters: int, use_kernel: bool):
    """The jitted step of the biased placer, named ``place`` (``jit_place``
    in a trace): ``(cand_idx, cand_q, u_edge, sm_service, sm_r, R,
    resident, stickiness) ->`` a dict of ``x``, ``sigma``, ``loads``,
    ``new_loads`` (``x & ~resident``), ``evicted`` (``resident & ~x``),
    ``evictions`` and the greedy loop's counts. The sparse EGP runs with
    the bias ``stickiness`` on every resident implementation, as
    :func:`repro.core.dynamic._egp_with_bias` adds it."""
    import jax
    import jax.numpy as jnp

    from repro.core.placement import _egp_place_sparse, sigma_sparse_jnp

    def place(cand_idx, cand_q, u_edge, sm_service, sm_r, R, resident,
              stickiness):
        bias = jnp.where(resident, stickiness, 0.0).astype(jnp.float32)
        x, info = _egp_place_sparse(
            cand_idx, cand_q, u_edge, sm_service, sm_r, R,
            max_iters=max_iters, use_kernel=use_kernel, with_trace=False,
            bias=bias)
        sigma = sigma_sparse_jnp(cand_idx, cand_q, u_edge, x)
        new_loads = x & ~resident
        evicted = resident & ~x
        return {"x": x, "sigma": sigma,
                "loads": new_loads.sum(dtype=jnp.int32),
                "new_loads": new_loads, "evicted": evicted,
                "evictions": evicted.sum(dtype=jnp.int32),
                "n_iters": info["n_iters"],
                "n_rescores": info["n_rescores"],
                "n_group_users": info["n_group_users"]}

    return jax.jit(place)


def placer_inputs(inst: PIESInstance, n_slots: int):
    """``(JaxInstance, impl table)`` of ``inst`` with its users padded to
    ``n_slots`` (:meth:`PIESInstance.as_jax`). The table has a row, empty,
    for the padded slots' service: they get no candidate pair, so they
    change no decision and add nothing to σ."""
    from repro.core.candidates import impl_table_np

    return (inst.as_jax(n_slots),
            impl_table_np(inst.sm_service, inst.n_service_ids + 1))


def place_on_device(inst: PIESInstance, resident, stickiness: float,
                    switching_cost: float) -> Dict:
    """One hysteresis step of :class:`repro.core.dynamic.DynamicPlacer` on
    the device: the users padded to :func:`user_size_class`, the candidate
    QoS of every eligible implementation, then :func:`placer_program`.

    ``resident`` is the ``[E, P]`` bool resident set, a device array or
    one that is copied there. Returns the program's outputs on the host
    (``x``, ``new_loads`` and ``evicted`` as NumPy bool arrays, the rest as
    numbers), ``value`` (σ − switching_cost · loads, in float64) and, under
    ``"resident"``, the placement ``x`` left on the device for the next
    step. The Pallas kernels run on a TPU, their jnp references elsewhere.

    With a tracer enabled its host phases are spans: ``placer.pad`` (the
    padded user arrays to the device, and the implementation table),
    ``placer.candidates`` (the candidate build), ``placer.greedy``
    (dispatch of ``jit_place``) and ``placer.wait`` (the host blocks on
    the results); and the step adds to the counters ``placement.loads``,
    ``placement.evictions``, ``placement.pad_users``,
    ``placement.user_slots``, ``placement.greedy_iters``,
    ``placement.greedy_rescores`` and ``placement.greedy_group_users``.
    """
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.kernels.qos_matrix.ops import qos_candidates_from_instance

    use_kernel = jax.default_backend() == "tpu"
    n_slots = user_size_class(inst.U)
    with obs.span("placer.pad"):
        ji, table = placer_inputs(inst, n_slots)
    with obs.span("placer.candidates"):
        cand_idx, cand_q = qos_candidates_from_instance(
            ji, table, None, use_kernel=use_kernel)
    with obs.span("placer.greedy"):
        out = placer_program(inst.P + 1, use_kernel)(
            cand_idx, cand_q, ji.u_edge, ji.sm_service, ji.sm_r, ji.R,
            jnp.asarray(resident, bool), jnp.float32(stickiness))
    with obs.span("placer.wait"):
        host = jax.device_get(out)
    host["value"] = float(host["sigma"]) - switching_cost * int(host["loads"])
    tracer = obs.get_tracer()
    if tracer is not None:
        tracer.count("placement.loads", int(host["loads"]))
        tracer.count("placement.evictions", int(host["evictions"]))
        tracer.count("placement.pad_users", n_slots - inst.U)
        tracer.count("placement.user_slots", n_slots)
        tracer.count("placement.greedy_iters", int(host["n_iters"]))
        tracer.count("placement.greedy_rescores", int(host["n_rescores"]))
        tracer.count("placement.greedy_group_users",
                     int(host["n_group_users"]))
    host["resident"] = out["x"]
    return host


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
