"""The float64 reference agrees with the program's own host algorithms
(``repro.core``: ``egp_np``, ``agp_np``, ``evaluate_host``) on seeded
instances, and its draws are the program's §VI-B draws."""
import numpy as np
import pytest

from bench import harness
from bench import reference as ref
from bench import traffic_gen


@pytest.fixture(scope="module")
def paper_dep():
    return harness.load_json(harness.BENCH / "configs" / "vib-paper.json"
                             )["deployment"]


@pytest.mark.parametrize("seed,n_users", [(0, 100), (7, 300), (2**31 + 5, 1000)])
def test_draws_are_the_programs(paper_dep, seed, n_users):
    from repro.core.instance import synthetic_instance

    inst = synthetic_instance(n_users=n_users, seed=seed)
    fleet, users = ref.draw_trial(seed, n_users, paper_dep)
    for a, b in [(inst.K, fleet.K), (inst.W, fleet.W), (inst.R, fleet.R),
                 (inst.sm_service, fleet.sm_service),
                 (inst.sm_acc, fleet.sm_acc), (inst.sm_k, fleet.sm_k),
                 (inst.sm_w, fleet.sm_w), (inst.sm_r, fleet.sm_r),
                 (inst.u_edge, users.edge), (inst.u_service, users.service),
                 (inst.u_alpha, users.alpha), (inst.u_delta, users.delta)]:
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("algo", ["egp", "agp"])
@pytest.mark.parametrize("seed", range(8))
def test_matches_host_algorithms(paper_dep, algo, seed):
    from repro.core.instance import synthetic_instance
    from repro.core.placement import agp_np, egp_np
    from repro.core.qos import qos_matrix_np
    from repro.workloads import evaluate_host

    n_users = (100, 400, 1000)[seed % 3]
    inst = synthetic_instance(n_users=n_users, seed=seed)
    fleet, users = ref.draw_trial(seed, n_users, paper_dep)
    x, s = ref.place(algo, fleet, users)
    want = {"egp": egp_np, "agp": agp_np}[algo](inst, qos_matrix_np(inst))
    np.testing.assert_array_equal(x, want)
    assert s == pytest.approx(float(evaluate_host([inst], algo)[0]),
                              rel=1e-12)
    assert ref.storage_overflow(fleet, x) == 0


def test_pair_qos_is_the_qos_matrix(paper_dep):
    from repro.core.instance import synthetic_instance
    from repro.core.qos import qos_matrix_np

    inst = synthetic_instance(n_users=500, seed=3)
    fleet, users = ref.draw_trial(3, 500, paper_dep)
    cand, Q = ref.pair_qos(fleet, users)
    dense = qos_matrix_np(inst)
    rows = np.broadcast_to(np.arange(500)[:, None], cand.shape)
    ok = cand >= 0
    np.testing.assert_allclose(Q[ok], dense[rows[ok], cand[ok]], rtol=0,
                               atol=0)
    # every eligible pair is among the candidates
    assert ok.sum() == (dense > 0).sum() + ((dense == 0) & (
        inst.u_service[:, None] == inst.sm_service[None, :])).sum()


def test_metro_egp_matches_host_on_a_slice():
    """At a metro fleet cut to 20 edges, the edge-by-edge greedy makes
    ``egp_np``'s placement."""
    from repro.core.instance import PIESInstance
    from repro.core.placement import egp_np

    dep = harness.load_json(harness.BENCH / "configs" / "vib-metro.json"
                            )["deployment"]
    dep["edges"]["count"] = 20
    dep["users"]["per_tick"] = 20000
    fleet = ref.draw_fleet(np.random.default_rng(dep["catalog_seed"]), dep)
    users = traffic_gen.populations(fleet, dep, {}, 11, 0, 1)[0]
    inst = PIESInstance(K=fleet.K, W=fleet.W, R=fleet.R,
                        sm_service=fleet.sm_service, sm_acc=fleet.sm_acc,
                        sm_k=fleet.sm_k, sm_w=fleet.sm_w, sm_r=fleet.sm_r,
                        u_edge=users.edge, u_service=users.service,
                        u_alpha=users.alpha, u_delta=users.delta,
                        delta_max=fleet.delta_max)
    x, _ = ref.place("egp", fleet, users)
    np.testing.assert_array_equal(x, egp_np(inst))


def test_bfloat16_control_departs(paper_dep):
    """The control is not the reference: at the paper's sizes its σ moves
    by far more than float32 rounding would."""
    gaps = []
    for seed in range(6):
        fleet, users = ref.draw_trial(seed, 1000, paper_dep)
        _, s64 = ref.place("egp", fleet, users)
        _, s16 = ref.place("egp", fleet, users, "bfloat16")
        gaps.append(abs(s16 - s64) / s64)
    assert max(gaps) > 1e-4, gaps


@pytest.mark.parametrize("kind", [{"kind": "zipf", "s": 1.1},
                                  {"kind": "hotspot", "hot_fraction": 0.1,
                                   "hot_share": 0.5}])
def test_popularity_mixes(kind):
    rng = np.random.default_rng(0)
    draws = ref.popularity(rng, 100, 200000, kind)
    share = np.bincount(draws, minlength=100) / draws.size
    if kind["kind"] == "zipf":
        assert share[0] > 5 * share[50]
    else:
        assert share[:10].sum() == pytest.approx(0.5, abs=0.01)
