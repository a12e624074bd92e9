"""The cell of the hysteresis placer (``azure-fleet-day``) at a tiny size
on the CPU: whole runs of the harness, its traffic, its reference, its
readers, and the faults and the control that must read not correct."""
import numpy as np
import pytest

from bench import harness
from bench import reference as ref
from bench import reference_hysteresis as ref_h
from bench.tests.conftest import tiny_cell
from bench.tests.test_drivers import SEED, drive, refused, run
from bench.trace_reduce import Device, Events, TraceSummary

TINY_USERS = [1500, 1100, 2600, 1900]


@pytest.fixture
def azure_cell(manifest):
    return tiny_cell(manifest, "azure-fleet-day", {"edges.count": 40},
                     {"users": TINY_USERS,
                      "failure": {"ticks": [2], "edges": 3},
                      "checked_ticks": 2})


# -- azure-fleet-day -------------------------------------------------------

def test_volume_is_the_excerpt_in_two_hour_buckets(manifest):
    mix = harness.load_cell(manifest, "azure-fleet-day").traffic
    rows = np.loadtxt(harness.ROOT / "examples" / "data"
                      / "azure_function_excerpt.csv", delimiter=",",
                      skiprows=1)
    buckets = np.bincount((rows[:, 0] // 120).astype(int), weights=rows[:, 1])
    assert buckets.size == 24
    np.testing.assert_array_equal(
        mix["users"], np.round(1e6 * buckets / buckets.max()).astype(int))


def test_hysteresis_tick_run(azure_cell):
    line = run(azure_cell)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == 1
    # peak_hbm_gb too on the chip; the CPU reports no allocator peak
    assert set(line["metrics"]) == {"tick_ms", "setup_s"}
    assert line["window"]["compiles"] == 0
    assert set(line["checks"]) == set(azure_cell.limits) == {
        "x_edges_differ", "loads_differ", "sigma_rel_gap",
        "storage_overflow"}


def test_hysteresis_tick_through_a_failure(azure_cell):
    """A cycle and more, so that the first failure tick is checked, with
    the resident set the program's own previous tick left."""
    import jax

    drv, items, numbers, failed, correct = drive(azure_cell, steps=6)
    assert items == 6 and failed == 0 and correct, numbers
    assert numbers["x_edges_differ"] == 0 and numbers["loads_differ"] == 0
    sample = drv.sample()
    assert 2 in sample and len(sample) == 3
    a = drv.answers[2]
    assert a.tick == 2 and not a.x[drv.failed].any()
    assert drv.answers[0].resident is not None
    np.testing.assert_array_equal(drv.answers[3].resident, a.x)
    # the placer went to the device
    assert not isinstance(drv.placer._resident, np.ndarray)
    assert drv.facts()["failed_edges"] == 3
    assert jax.devices()[0].platform == "cpu"


def test_the_cycle(azure_cell):
    """Uniform edges, Zipf services whose ranking rotates a service a
    tick, and on the failure tick no user and no storage at a failed
    edge, their users on the next live edge."""
    import jax

    drv = azure_cell.driver().Driver(azure_cell, SEED, jax.devices()[:1])
    assert [u.edge.size for u in drv.users] == TINY_USERS
    for t, u in enumerate(drv.users):
        top = np.bincount(u.service, minlength=100).argmax()
        assert top == t % 100
        at_failed = drv.failed[u.edge].sum()
        assert (at_failed == 0) == (t == 2)
        assert (drv.fleets[t].R[drv.failed] == 0).all() == (t == 2)
    assert drv.failed.sum() == 3


def test_ring_successor():
    mod = harness.load_module(harness.BENCH / "drivers" / "hysteresis_tick.py",
                              "hysteresis_tick_test")
    failed = np.array([False, True, True, False, True])
    np.testing.assert_array_equal(mod.ring_successor(failed), [0, 3, 3, 3, 0])


def test_the_seed_picks_only_the_checked_ticks(azure_cell):
    import jax

    azure_cell.traffic["checked_ticks"] = 3
    a, b = (azure_cell.driver().Driver(azure_cell, s, jax.devices()[:1])
            for s in (SEED, SEED + 1))
    for ua, ub in zip(a.users, b.users):
        np.testing.assert_array_equal(ua.alpha, ub.alpha)
        np.testing.assert_array_equal(ua.edge, ub.edge)
    for d in (a, b):
        d.warm()
        for _ in range(12):
            d.step()
    assert a.sample() != b.sample()
    for d in (a, b):
        assert 2 in d.sample() and len(d.sample()) == 4


def _plant(monkeypatch, fault):
    """``DynamicPlacer.step`` with ``fault`` applied."""
    from repro.core import dynamic

    real = dynamic.DynamicPlacer.step

    def broken(self, inst, Q=None):
        if fault == "unbiased":
            saved, self.stickiness = self.stickiness, 0.0
            try:
                return real(self, inst, Q)
            finally:
                self.stickiness = saved
        x, value, loads = real(self, inst, Q)
        x = x.copy()
        if fault == "altered":
            e = int(np.argmax(x.sum(axis=1)))
            x[e, np.nonzero(x[e])[0][0]] = False
        elif fault == "loads":
            self.new_loads = np.zeros_like(self.new_loads)
        elif fault == "failed_edge":
            x[:, int(np.argmin(inst.sm_r))] |= inst.R == 0
        elif fault == "unchanged":
            x[:] = False
            value = 0.0
        return x, value, loads

    monkeypatch.setattr(dynamic.DynamicPlacer, "step", broken)


@pytest.mark.parametrize("fault", ["unchanged", "unbiased", "altered",
                                   "loads"])
def test_hysteresis_tick_fault_fails(azure_cell, monkeypatch, fault):
    """A run whose first tick starts from the set-up's resident set."""
    _plant(monkeypatch, fault)
    assert refused(run(azure_cell))


def test_hysteresis_tick_failed_edge_fault_fails(azure_cell, monkeypatch):
    _plant(monkeypatch, "failed_edge")
    drv, items, numbers, failed, correct = drive(azure_cell, steps=3)
    assert not correct and failed and numbers["storage_overflow"] == 3


def test_hysteresis_tick_control_fails(azure_cell):
    """The reference in bfloat16 as the placer: benefits and re-scores
    rounded to bfloat16 tie and swap."""
    import jax

    from bench.control_hysteresis import plant_control

    drv = azure_cell.driver().Driver(azure_cell, SEED, jax.devices()[:1])
    plant_control(azure_cell, drv)
    for _ in range(3):
        drv.step()
    numbers, failed = drv.check()
    assert failed and not harness.judge(numbers, azure_cell.limits)[0]
    assert numbers["x_edges_differ"] >= 1


# -- the reference ---------------------------------------------------------

@pytest.fixture(scope="module")
def paper_dep():
    return harness.load_json(harness.BENCH / "configs" / "vib-paper.json"
                             )["deployment"]


@pytest.mark.parametrize("seed", range(4))
def test_reference_is_the_programs_biased_greedy(paper_dep, seed):
    """The float64 reference makes ``_egp_with_bias``'s placement from a
    resident set, and with no bonus ``reference.egp``'s."""
    from repro.core.dynamic import _egp_with_bias
    from repro.core.instance import synthetic_instance
    from repro.core.qos import qos_matrix_np

    n_users = (300, 1000)[seed % 2]
    inst = synthetic_instance(n_users=n_users, seed=seed)
    fleet, users = ref.draw_trial(seed, n_users, paper_dep)
    resident = np.random.default_rng(seed).random((fleet.E, fleet.P)) < 0.2
    r = ref_h.place(fleet, users, resident, 3.0, 2.0)
    np.testing.assert_array_equal(
        r["x"], _egp_with_bias(inst, qos_matrix_np(inst), resident, 3.0))
    assert r["loads"] == (r["x"] & ~resident).sum()
    assert r["value"] == r["sigma"] - 2.0 * r["loads"]
    plain = ref_h.place(fleet, users, resident, 0.0, 2.0)
    np.testing.assert_array_equal(plain["x"], ref.place("egp", fleet,
                                                        users)[0])
    assert (r["x"] != plain["x"]).any()     # the bonus moves picks


# -- the readers -------------------------------------------------------------

@pytest.fixture
def hyst_run(manifest):
    """Two ticks in a 1000 ns window; on device 0 the candidate build at
    [50, 100) and [550, 600), the placer at [100, 400) and [600, 850)."""
    mods = Events.make([(50, 50, "jit_qos_candidates(1)"),
                        (100, 300, "jit_place(2)"),
                        (550, 50, "jit_qos_candidates(1)"),
                        (600, 250, "jit_place(2)")])
    host = Events.make([(0, 1000, "bench.window")])
    trace = TraceSummary(devices=[Device(0, mods, Events.make([]))],
                         host=host, window=(0, 1000))
    cell = harness.load_cell(manifest, "azure-fleet-day")
    return harness.Run(cell=cell, setup_s=30.0, window_s=1.0, steps=2,
                       items=2, facts={}, memory_peak_bytes=None,
                       trace=trace, peaks={"hbm_bytes_per_s": 819e9})


def _counters(**counts):
    from repro import obs

    tracer = obs.enable()
    for name, n in counts.items():
        tracer.count(f"placement.{name}", n)
    obs.disable()


def test_hyst_readers(hyst_run):
    read = lambda name: hyst_run.cell.reader(name).read(hyst_run)  # noqa
    # busy [50, 400) ∪ [550, 850) = 650 of 1000 ns
    assert read("device_idle_pct.hyst") == pytest.approx(35.0)
    assert read("placer_ms.hyst") == pytest.approx(275e-6)
    _counters(pad_users=30, user_slots=200, loads=14)
    assert read("pad_pct.hyst") == pytest.approx(15.0)
    assert read("loads.hyst") == pytest.approx(7.0)
    assert {m["name"] for m in hyst_run.cell.per_layer} == {
        "device_idle_pct.hyst", "placer_ms.hyst", "pad_pct.hyst",
        "loads.hyst"}


def test_hyst_readers_silent_on_a_program_without_the_placer(hyst_run):
    """The parent's program: no ``jit_place``, no placer counters."""
    hyst_run.trace.devices[0].modules = Events.make(
        [(100, 300, "jit_run(2)")])
    _counters(greedy_iters=400)
    for name in ("placer_ms.hyst", "pad_pct.hyst", "loads.hyst"):
        assert hyst_run.cell.reader(name).read(hyst_run) is None
    hyst_run.trace = None
    for m in hyst_run.cell.per_layer:
        assert hyst_run.cell.reader(m["name"]).read(hyst_run) is None


def test_control_script_knows_only_the_new_drivers(manifest):
    from bench.control_hysteresis import plant_control

    with pytest.raises(ValueError):
        plant_control(harness.load_cell(manifest, "metro-tick"), None)
