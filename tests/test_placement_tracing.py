"""The placement path's measurement points: the sparse greedy's phase
scopes in the compiled program, its loop counters, the host spans of the
sparse tick and of the sweeps engine's chunks — and that none of them
changes an answer."""
import re

import numpy as np
import pytest

from repro import obs
from repro.core.candidates import impl_table_np
from repro.core.instance import synthetic_instance
from repro.core.placement import egp_place_sparse_jax
from repro.kernels.qos_matrix.ops import qos_candidates_from_instance
from repro.workloads import evaluate_sparse
from repro.workloads.batched import sparse_evaluator

PHASES = ("greedy.init", "greedy.pick", "greedy.rescore", "greedy.stop_test",
          "sigma")


@pytest.fixture(autouse=True)
def _obs_off():
    assert not obs.enabled()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def tick():
    inst = synthetic_instance(400, n_edges=4, n_services=20, seed=3)
    ji = inst.as_jax()
    table = impl_table_np(inst.sm_service, inst.S)
    cand_idx, cand_q = qos_candidates_from_instance(ji, table, None,
                                                    use_kernel=False)
    args = (cand_idx, cand_q, ji.u_edge, ji.sm_service, ji.sm_r, ji.R)
    return inst, args


def test_compiled_tick_carries_phase_scopes(tick):
    inst, args = tick
    text = sparse_evaluator(inst.P + 1, False).lower(*args).compile() \
        .as_text()
    ops = re.findall(r"= \S+ ([\w-]+)\(.*op_name=\"([^\"]*)\"", text)
    scopes = {phase for _, name in ops for phase in PHASES
              if f"/{phase}/" in name}
    assert scopes == set(PHASES), sorted(scopes)
    # the re-score's gathers of its groups' users and its `satisfied`
    # scatter keep their scope inside the cond branch
    for op in ("gather", "scatter"):
        assert any(kind == op and "/greedy.rescore/" in name
                   and "branch" in name for kind, name in ops), op


def test_counters_match_the_decision_trace(tick):
    inst, args = tick
    _, trace = egp_place_sparse_jax(*args, max_iters=inst.P + 1,
                                    with_trace=True)
    n_iters = int(trace["n_iters"])
    placed_iters = int(np.asarray(trace["placed"])[:n_iters]
                       .any(axis=1).sum())
    assert int(trace["n_rescores"]) == placed_iters
    tr = obs.enable()
    evaluate_sparse([inst, inst])
    obs.disable()
    assert tr.counters["placement.greedy_iters"] == 2 * n_iters
    assert tr.counters["placement.greedy_rescores"] == 2 * placed_iters
    assert 0 < placed_iters < n_iters <= inst.P + 1


def test_counts_and_tracing_change_no_answer(tick):
    inst, args = tick
    x_plain = egp_place_sparse_jax(*args, max_iters=inst.P + 1)
    x_traced, trace = egp_place_sparse_jax(*args, max_iters=inst.P + 1,
                                           with_trace=True)
    assert np.array_equal(np.asarray(x_plain), np.asarray(x_traced))
    # the jitted tick returns the same placement and the loop's counts
    _, x_run, n_iters, n_rescores, n_group = sparse_evaluator(
        inst.P + 1, False)(*args)
    assert np.array_equal(np.asarray(x_run), np.asarray(x_plain))
    assert int(n_iters) == int(trace["n_iters"])
    assert int(n_rescores) == int(trace["n_rescores"])
    assert int(n_group) == int(trace["n_group_users"])
    v_off, x_off = evaluate_sparse([inst])
    obs.enable()
    v_on, x_on = evaluate_sparse([inst])
    obs.disable()
    assert v_off.tobytes() == v_on.tobytes()
    assert np.asarray(x_off[0]).tobytes() == np.asarray(x_on[0]).tobytes()


def test_sparse_tick_host_spans(tick):
    inst, _ = tick
    tr = obs.enable()
    evaluate_sparse([inst])
    obs.disable()
    doc = tr.snapshot()
    names = [doc["names"][i] for i in doc["spans"]["name"]]
    assert names == ["placement.as_jax", "placement.candidates",
                     "placement.greedy", "placement.wait"]
    assert not any(n.startswith("kernel.") for n in doc["names"])


def test_last_tracer_outlives_disable():
    tr = obs.enable()
    obs.count("c", 2)
    assert obs.disable() is tr
    assert obs.last_tracer() is tr and tr.counters["c"] == 2
    assert obs.disable() is None and obs.last_tracer() is tr


def test_sweep_chunk_phases_nest_under_the_chunk():
    from repro.sweeps import SweepSpec, run_sweep

    grid = ({"n_users": 30, "n_edges": 3, "n_services": 8, "max_impls": 3},
            {"n_users": 60, "n_edges": 3, "n_services": 8, "max_impls": 3})
    spec = SweepSpec(scenarios=("synthetic",), seeds=(0, 1), n_ticks=1,
                     algos=("egp",), override_grid=grid)
    tr = obs.enable()
    run_sweep(spec)
    obs.disable()
    doc = tr.snapshot()
    s = doc["spans"]
    rows = [(doc["names"][n], t0, t1, tid, d) for n, t0, t1, tid, d in
            zip(s["name"], s["t0_ns"], s["t1_ns"], s["tid"], s["depth"])]
    chunks = [r for r in rows if r[0] == "sweep.chunk"]
    phases = [r for r in rows
              if r[0] in ("sweep.pad", "sweep.dispatch", "sweep.wait")]
    assert chunks and {r[0] for r in phases} == {
        "sweep.pad", "sweep.dispatch", "sweep.wait"}
    for name, t0, t1, tid, depth in phases:
        assert any(c[1] <= t0 and t1 <= c[2] and c[3] == tid
                   and c[4] < depth for c in chunks), name
