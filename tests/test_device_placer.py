"""The hysteresis placer on the device: the same placements as the float64
host loop ``_egp_with_bias`` that ``DynamicPlacer`` runs below its
crossover, pick for pick, tick after tick, with its users padded to a
size class and its resident set kept on the device."""
import dataclasses
import math

import numpy as np
import pytest

from repro import obs
from repro.core import dynamic
from repro.core.dynamic import DynamicPlacer, _egp_with_bias
from repro.core.instance import synthetic_instance
from repro.core.placement import _egp_place_sparse, egp_np
from repro.core.qos import qos_matrix_np
from repro.kernels.qos_matrix.ops import qos_candidates_from_instance
from repro.workloads.batched import (USER_CLASS_TOP, place_on_device,
                                     placer_inputs, placer_program,
                                     user_size_class)

CATALOG = synthetic_instance(10, n_edges=8, n_services=30, seed=4)


def _users(seed, n=2000, kind="uniform"):
    """``n`` users on :data:`CATALOG`'s fleet, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    inst = CATALOG
    if kind == "zipf":
        pop = 1.0 / np.arange(1, inst.S + 1) ** 1.1
        svc = rng.choice(inst.S, size=n, p=pop / pop.sum())
    else:
        svc = rng.integers(0, inst.S, size=n)
    return dataclasses.replace(
        inst, u_edge=rng.integers(0, inst.E, size=n), u_service=svc,
        u_alpha=1.0 - np.clip(rng.exponential(0.125, n), 0.0, 1.0),
        u_delta=np.clip(rng.exponential(1.5, n), 0.0, inst.delta_max))


def _no_impls(seed):
    """Two services lose their implementations: their users have none."""
    inst = _users(seed)
    keep = ~np.isin(inst.sm_service, (3, inst.S - 1))
    return dataclasses.replace(
        inst, **{f: getattr(inst, f)[keep] for f in
                 ("sm_service", "sm_acc", "sm_k", "sm_w", "sm_r")})


def _failed(seed):
    """Two edges have no storage; the users of one move to the next edge,
    those of the other stay."""
    inst = _users(seed, kind="zipf")
    R = inst.R.copy()
    R[[2, 5]] = 0.0
    u_edge = np.where(inst.u_edge == 2, 3, inst.u_edge)
    return dataclasses.replace(inst, R=R, u_edge=u_edge)


KINDS = {"uniform": _users, "zipf": lambda s: _users(s, kind="zipf"),
         "no_impls": _no_impls, "failed": _failed}


def _resident(inst, seed, share=0.15):
    return np.random.default_rng(seed + 1000).random((inst.E, inst.P)) < share


@pytest.mark.parametrize("bonus", [0.0, 3.0])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_device_placer_is_the_biased_host_greedy(kind, bonus):
    """``x`` pick for pick, and the tick's loads and evictions, from a
    random resident set."""
    for seed in range(2):
        inst = KINDS[kind](seed)
        resident = _resident(inst, seed)
        want = _egp_with_bias(inst, qos_matrix_np(inst), resident, bonus)
        out = place_on_device(inst, resident, bonus, 2.0)
        np.testing.assert_array_equal(out["x"], want)
        np.testing.assert_array_equal(out["new_loads"], want & ~resident)
        np.testing.assert_array_equal(out["evicted"], resident & ~want)
        assert out["loads"] == int((want & ~resident).sum())
        np.testing.assert_array_equal(np.asarray(out["resident"]), want)
        if kind == "failed":
            assert not want[[2, 5]].any()


def test_bonus_zero_is_egp():
    for seed in range(3):
        inst = _users(seed, kind="zipf")
        out = place_on_device(inst, _resident(inst, seed), 0.0, 2.0)
        np.testing.assert_array_equal(out["x"], egp_np(inst))


def _horizon(n_ticks=6):
    """Populations that change in size, skew and failures, tick to tick."""
    out = []
    for t in range(n_ticks):
        inst = _users(100 + t, n=(1500, 2600, 900, 3100, 2000, 1200)[t % 6],
                      kind="zipf" if t % 2 else "uniform")
        if t == 3:
            inst = dataclasses.replace(inst, R=np.where(
                np.arange(inst.E) == 1, 0.0, inst.R))
        out.append(inst)
    return out


def test_horizon_matches_the_host_path(monkeypatch):
    """Six ticks of one ``DynamicPlacer`` on each path from the same start:
    the same placements, loads, masks and values, the resident set carried
    on the device by the one and on the host by the other."""
    ticks = _horizon()
    host, dev = DynamicPlacer(), DynamicPlacer()
    for inst in ticks:
        monkeypatch.setattr(dynamic, "DEVICE_MIN_CELLS", math.inf)
        xh, vh, lh = host.step(inst)
        monkeypatch.setattr(dynamic, "DEVICE_MIN_CELLS", 0)
        xd, vd, ld = dev.step(inst)
        np.testing.assert_array_equal(xd, xh)
        assert ld == lh
        np.testing.assert_array_equal(dev.new_loads, host.new_loads)
        np.testing.assert_array_equal(dev.evicted, host.evicted)
        # σ in float32 on the device, float64 on the host
        assert vd == pytest.approx(vh, rel=1e-6)
    assert not isinstance(dev._resident, np.ndarray)


def _placed(inst, n_slots, resident, bonus):
    ji, table = placer_inputs(inst, n_slots)
    cand_idx, cand_q = qos_candidates_from_instance(ji, table, None,
                                                    use_kernel=False)
    out = placer_program(inst.P + 1, False)(
        cand_idx, cand_q, ji.u_edge, ji.sm_service, ji.sm_r, ji.R,
        resident, np.float32(bonus))
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("kind", ["zipf", "no_impls", "failed"])
def test_padding_changes_no_decision(kind):
    """The users padded to their size class give the unpadded placement
    bit for bit, and σ up to the order of its float32 sum."""
    inst = KINDS[kind](7)
    resident = _resident(inst, 7)
    bare = _placed(inst, inst.U, resident, 3.0)
    padded = _placed(inst, user_size_class(inst.U), resident, 3.0)
    assert user_size_class(inst.U) > inst.U
    for k in ("x", "new_loads", "evicted", "loads", "n_iters"):
        np.testing.assert_array_equal(padded[k], bare[k])
    assert float(padded["sigma"]) == pytest.approx(float(bare["sigma"]),
                                                   rel=1e-6)


def test_padded_slots_are_inert():
    ji, table = placer_inputs(_users(3, n=100), 141)
    cand_idx, cand_q = qos_candidates_from_instance(ji, table, None,
                                                    use_kernel=False)
    assert (np.asarray(cand_idx)[100:] == -1).all()
    assert (np.asarray(cand_q)[100:] == 0).all()
    assert (np.asarray(ji.u_edge)[100:] == CATALOG.E).all()
    with pytest.raises(ValueError):
        placer_inputs(_users(3, n=100), 99)


def test_no_bias_and_zero_bias_agree():
    """A zero bias is the unbiased greedy."""
    import jax.numpy as jnp

    inst = _users(11, kind="zipf")
    ji, table = placer_inputs(inst, inst.U)
    cand_idx, cand_q = qos_candidates_from_instance(ji, table, None,
                                                    use_kernel=False)
    args = (cand_idx, cand_q, ji.u_edge, ji.sm_service, ji.sm_r, ji.R)
    kw = dict(max_iters=inst.P + 1, use_kernel=False, with_trace=False)
    plain, _ = _egp_place_sparse(*args, **kw)
    zero, _ = _egp_place_sparse(*args, **kw,
                                bias=jnp.zeros((inst.E, inst.P)))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(zero))


def test_user_size_classes():
    assert user_size_class(USER_CLASS_TOP) == USER_CLASS_TOP
    assert user_size_class(USER_CLASS_TOP + 1) == math.ceil(
        USER_CLASS_TOP * math.sqrt(2))
    assert user_size_class(0) == 0 and user_size_class(1) == 1
    sizes = np.unique(np.geomspace(1e5, 1e6, 2000).astype(int))
    classes = [user_size_class(int(n)) for n in sizes]
    assert all(c >= n for c, n in zip(classes, sizes))
    assert all(c <= math.sqrt(2) * n + 1 for c, n in zip(classes, sizes))
    assert classes == sorted(classes) and len(set(classes)) <= 8
    for n in (2, 3, 17, 1000, 123_457, 10**7):
        c = user_size_class(n)
        assert c >= n and user_size_class(c) == c
        assert user_size_class(c + 1) > c


def test_step_takes_the_path_its_size_asks_for(monkeypatch):
    """``DynamicPlacer.step`` places on the device from U·P =
    ``DEVICE_MIN_CELLS`` on, and on the host below it."""
    import repro.workloads.batched as batched

    calls = []
    real_dev, real_host = batched.place_on_device, dynamic._egp_with_bias
    monkeypatch.setattr(batched, "place_on_device", lambda *a, **k: (
        calls.append("device"), real_dev(*a, **k))[1])
    monkeypatch.setattr(dynamic, "_egp_with_bias", lambda *a, **k: (
        calls.append("host"), real_host(*a, **k))[1])
    inst = _users(5, n=300)
    placer = DynamicPlacer()
    for threshold, want in ((inst.U * inst.P, "device"),
                            (inst.U * inst.P + 1, "host"),
                            (1, "device")):
        monkeypatch.setattr(dynamic, "DEVICE_MIN_CELLS", threshold)
        placer.step(inst)
        assert calls[-1] == want
    assert calls == ["device", "host", "device"]


def test_counters_agree_with_the_masks():
    inst = _users(9, n=700, kind="zipf")
    resident = _resident(inst, 9)
    tracer = obs.enable()
    try:
        out = place_on_device(inst, resident, 3.0, 2.0)
    finally:
        obs.disable()
    c = tracer.counters
    assert c["placement.loads"] == out["new_loads"].sum() == out["loads"]
    assert c["placement.evictions"] == out["evicted"].sum()
    assert c["placement.user_slots"] == user_size_class(700)
    assert c["placement.pad_users"] == user_size_class(700) - 700
    assert c["placement.greedy_iters"] == out["n_iters"] > 0
    assert c["placement.greedy_rescores"] == out["n_rescores"]
    assert c["placement.greedy_group_users"] == out["n_group_users"]
    assert out["value"] == pytest.approx(
        float(out["sigma"]) - 2.0 * out["loads"], abs=0)
    spans = set(tracer.snapshot()["names"])
    assert {"placer.pad", "placer.candidates", "placer.greedy",
            "placer.wait"} <= spans
