"""Each driver at a tiny size on the CPU: whole runs of the harness past
its look for a chip (set-up, window, metrics, the comparison with the
reference, the result line), sound, with the control (the reference in
bfloat16 put in the program's place) and with each fault the cell can
have planted under the timed path. The last two must read not correct."""
import json
import math
import time

import numpy as np
import pytest

from bench import control, harness

SEED = 2**31 + 12345  # the driver's seeds are large


def run(cell, seconds=0.0, seed=SEED):
    """One whole run on the CPU; ``seconds=0`` makes a window of one step."""
    import jax

    line = harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            jax.devices()[:1], {"hbm_bytes_per_s": 819e9})
    json.dumps(line)                       # the line is plain JSON
    assert list(line)[-1] == "checks"      # the numbers compared come last
    return line


def drive(cell, steps=2, seed=SEED):
    import jax

    drv = cell.driver().Driver(cell, seed, jax.devices()[:1])
    drv.warm()
    items = sum(drv.step() for _ in range(steps))
    numbers, failed = drv.check()
    correct, checks = harness.judge(numbers, cell.limits)
    return drv, items, numbers, failed, correct


def refused(line):
    return not line["correct"] and line["failed"] > 0


# -- metro-tick ------------------------------------------------------------

def test_sparse_tick_run(metro_cell):
    line = run(metro_cell)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == 1
    assert set(line["metrics"]) >= {"tick_ms", "setup_s"}
    assert line["window"]["compiles"] == 0
    assert set(line["checks"]) == set(metro_cell.limits)


def test_sparse_tick_is_correct(metro_cell):
    drv, items, numbers, failed, correct = drive(metro_cell)
    assert items == 2 and failed == 0 and correct, numbers
    assert numbers["x_edges_differ"] == 0
    assert numbers["storage_overflow"] == 0
    facts = drv.facts()
    assert facts["users"] == 3000 and facts["edges"] == 30
    assert facts["ticks"] == 2


def test_sparse_tick_serves_one_pool_in_the_seeds_order(metro_cell):
    """Every seed serves the same populations (the same work), each once
    per cycle, in an order the seed sets."""
    import jax

    metro_cell.traffic["populations"] = 6
    a, b, c = (metro_cell.driver().Driver(metro_cell, s, jax.devices()[:1])
               for s in (SEED, SEED, SEED + 1))
    assert np.array_equal(a.populations[0].alpha, c.populations[0].alpha)
    assert np.array_equal(a.order, b.order)
    assert not np.array_equal(a.order, c.order)
    assert sorted(a.order) == list(range(6))
    # no population repeats within a cycle, and the warm-up has its own
    alphas = [p.alpha[:50].tobytes() for p in a.populations]
    assert len(set(alphas)) == len(alphas) == 7


def _plant(monkeypatch, fault):
    """Replace ``evaluate_sparse`` by the program with ``fault`` applied to
    what it returns (or to what it is given)."""
    import dataclasses

    import repro.workloads as W

    real = W.evaluate_sparse

    def broken(instances, **kw):
        inst = instances[0]
        if fault == "half_batch":
            keep = np.arange(inst.U) < inst.U // 2
            inst = dataclasses.replace(
                inst, u_edge=inst.u_edge[keep],
                u_service=inst.u_service[keep],
                u_alpha=inst.u_alpha[keep], u_delta=inst.u_delta[keep])
        values, xs = real([inst], **kw)
        x = np.asarray(xs[0]).copy()
        if fault == "unchanged":
            x[:] = False
            values = np.zeros_like(values)
        elif fault == "half_batch":
            values = values * 2.0     # the mean over the half kept
        elif fault == "altered":
            e = int(np.argmax(x.sum(axis=1)))
            p = int(np.nonzero(x[e])[0][0])
            x[e, p] = False           # one placed implementation dropped
        return values, [x]

    monkeypatch.setattr(W, "evaluate_sparse", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_sparse_tick_fault_fails(metro_cell, monkeypatch, fault):
    _plant(monkeypatch, fault)
    line = run(metro_cell)
    assert refused(line), line["checks"]


def test_sparse_tick_control_fails(metro_cell, monkeypatch):
    """The float64 reference computed in bfloat16, in the program's place."""
    import jax

    import repro.workloads as W

    drv = metro_cell.driver().Driver(metro_cell, SEED, jax.devices()[:1])
    monkeypatch.setattr(W, "evaluate_sparse",
                        control.sparse_tick_control(drv.fleet))
    assert refused(run(metro_cell))


# -- paper-sweep-4chip -----------------------------------------------------

def test_sweep_run(sweep_cell):
    line = run(sweep_cell)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == 3 * 3 * 2
    assert set(line["metrics"]) == {"sweep_items_per_s", "setup_s"}
    assert line["window"]["compiles"] == 0


def test_sweep_is_correct(sweep_cell):
    drv, items, numbers, failed, correct = drive(sweep_cell)
    assert items == 2 * 3 * 3 * 2
    assert failed == 0 and correct, numbers
    assert numbers["items_missing"] == 0
    assert drv.facts()["passes"] == 2


def test_sweep_serves_one_pool_in_the_seeds_order(sweep_cell):
    """Every seed evaluates the same trials (the same work); the seed sets
    the order in which a pass visits the user counts."""
    import jax

    a, b, c = (sweep_cell.driver().Driver(sweep_cell, s, jax.devices()[:1])
               for s in (SEED, SEED, SEED + 1))
    assert a.trial_seeds == b.trial_seeds == c.trial_seeds
    assert len(set(a.trial_seeds)) == 3
    assert a.users == b.users and sorted(a.users) == sorted(c.users)
    assert any(a.spec(a.trial_seeds).override_grid
               != d.spec(d.trial_seeds).override_grid
               for d in (sweep_cell.driver().Driver(sweep_cell, SEED + k,
                                                    jax.devices()[:1])
                         for k in range(1, 6)))


def test_sweep_compiles_nothing_after_set_up(sweep_cell):
    """Set-up's one pass has compiled every program and met every chunk
    layout a pass makes, so a pass neither compiles nor re-runs a chunk."""
    import jax

    from repro.sweeps import shard

    drv = sweep_cell.driver().Driver(sweep_cell, SEED, jax.devices()[:1])
    drv.warm()
    layouts = len(shard._WARMED)
    counter = harness.CompileCounter()
    counter.active = True
    drv.step()
    drv.step()
    counter.active = False
    assert counter.counts["compiles"] == 0, counter.counts
    assert len(shard._WARMED) == layouts


def _plant_sweep(monkeypatch, fault):
    import repro.sweeps as S

    real = S.run_sweep

    def broken(spec, **kw):
        res = real(spec, **kw)
        keys = sorted(res.values)
        if fault == "unchanged":
            for k in keys:
                res.values[k] = np.zeros_like(res.values[k])
        elif fault == "half_batch":
            flat = np.concatenate([res.values[k][:, 0] for k in keys])
            mean = flat[: flat.size // 2].mean()
            for k in keys[len(keys) // 2:]:
                res.values[k] = np.full_like(res.values[k], mean)
        elif fault == "exchange":
            # only the first shard's results come back from the mesh
            for k in keys:
                v = res.values[k].copy()
                v[1:] = math.nan
                res.values[k] = v
        elif fault == "altered":
            for k in keys:
                res.values[k] = res.values[k] * (1 + 1e-3)
        return res

    monkeypatch.setattr(S, "run_sweep", broken)


@pytest.mark.parametrize("fault",
                         ["unchanged", "half_batch", "exchange", "altered"])
def test_sweep_fault_fails(sweep_cell, monkeypatch, fault):
    _plant_sweep(monkeypatch, fault)
    line = run(sweep_cell)
    assert refused(line), line["checks"]


def test_sweep_control_fails(sweep_cell, monkeypatch):
    """The reference in bfloat16 in the program's place: each item's σ."""
    import repro.sweeps as S

    monkeypatch.setattr(S, "run_sweep", control.sweep_control(
        sweep_cell.config["deployment"]))
    assert refused(run(sweep_cell))
