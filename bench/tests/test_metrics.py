"""Each metric reader on a run record whose trace is built by hand, so
that every number can be worked out on paper."""
import numpy as np
import pytest

from bench import harness
from bench.trace_reduce import (Device, Events, TraceSummary, gaps, leaves,
                                union_ns)


def events(rows, scope=None):
    """``rows``: (start, dur, name) in ns; ``scope`` maps a name to its
    scope path."""
    return Events.make((s, d, n, (scope or {}).get(n, "")) for s, d, n in rows)


def test_union_and_gaps():
    s = np.array([0, 5, 20, 22, 40])
    e = np.array([10, 12, 30, 25, 60])
    assert union_ns(s, e, 0, 100) == 12 + 10 + 20
    assert union_ns(s, e, 8, 50) == 4 + 10 + 10
    assert gaps(s, e, 0, 100) == [(12, 20), (30, 40), (60, 100)]
    assert union_ns(np.zeros(0, int), np.zeros(0, int), 0, 10) == 0


def test_leaves_drop_the_ops_that_hold_others():
    ev = events([(0, 100, "%while"), (10, 5, "%a"), (20, 5, "%b"),
                 (100, 5, "%c")])
    assert sorted(leaves(ev).name) == ["%a", "%b", "%c"]


@pytest.fixture
def tick_run(manifest):
    """Two ticks in a 1000 ns window; on device 0:
    greedy program jit_run at [100, 400) and [600, 900),
    the candidate program at [50, 100) and [550, 600), one idle rest."""
    mods = events([(50, 50, "jit_qos_candidates(1)"), (100, 300, "jit_run(2)"),
                   (550, 50, "jit_qos_candidates(1)"), (600, 300, "jit_run(2)")])
    scope = {"%cc": "jit(qos_candidates)/qos_candidates_pallas/x",
             "%gk": "jit(run)/while/body/greedy_argmax_pallas/y",
             "%pad": "jit(run)/while/body/greedy_argmax_pallas/pad"}
    ops = events([(60, 40, "%cc"), (560, 40, "%cc"),
                  (150, 10, "%gk"), (170, 10, "%gk"), (650, 10, "%gk"),
                  (140, 5, "%pad"), (640, 5, "%pad"), (645, 1, "%pad"),
                  (100, 300, "%while"), (600, 300, "%while"),
                  (50, 50, "%fusion"), (550, 10, "%fusion")], scope)
    host = events([(0, 1000, "bench.window"), (0, 500, "bench.tick"),
                   (500, 500, "bench.tick"), (0, 50, "PjitFunction(x)")])
    trace = TraceSummary(devices=[Device(0, mods, ops)], host=host,
                         window=(0, 1000))
    cell = harness.load_cell(manifest, "metro-tick")
    return harness.Run(cell=cell, setup_s=12.5, window_s=2.0, steps=2,
                       items=2, facts={"users": 1000, "max_impls": 10,
                                       "edges": 4, "impls": 25, "ticks": 2},
                       memory_peak_bytes=5_500_000_000, trace=trace,
                       peaks={"hbm_bytes_per_s": 819e9})


def read(run, name):
    return run.cell.reader(name).read(run)


def test_end_to_end_readers(tick_run):
    assert read(tick_run, "tick_ms") == pytest.approx(1000.0)
    assert read(tick_run, "setup_s") == 12.5
    assert read(tick_run, "peak_hbm_gb") == pytest.approx(5.5)
    assert read(tick_run, "sweep_items_per_s") == pytest.approx(1.0)


def test_tick_layer_readers(tick_run):
    # busy: [50, 400) ∪ [550, 900) = 700 of 1000 ns
    assert read(tick_run, "device_idle_pct.tick") == pytest.approx(30.0)
    assert read(tick_run, "greedy_ms.tick") == pytest.approx(300e-6)
    assert read(tick_run, "cand_ms.tick") == pytest.approx(50e-6)
    # 2 calls of 16·1000 + 20·1000·10 bytes over 80 ns of kernel time
    want = 100 * (2 * 216000 / 819e9) / 80e-9
    assert read(tick_run, "qos_candidates_roofline") == pytest.approx(want)
    # 3 calls (the most frequent op of the scope) of 8·4·25 bytes over
    # 30 + 11 ns
    want = 100 * (3 * 800 / 819e9) / 41e-9
    assert read(tick_run, "greedy_argmax_roofline") == pytest.approx(want)


def test_sweep_layer_readers(tick_run):
    assert read(tick_run, "device_idle_pct.sweep") == pytest.approx(30.0)
    assert read(tick_run, "evaluator_ms.sweep") == pytest.approx(700e-6 / 2)


def test_readers_without_a_trace_are_silent(tick_run):
    tick_run.trace = None
    for m in tick_run.cell.per_layer:
        assert read(tick_run, m["name"]) is None


def test_roofline_silent_without_its_kernel(tick_run):
    d = tick_run.trace.devices[0]
    d.ops = d.ops.select(np.array(["argmax" not in s for s in d.ops.scope]))
    assert read(tick_run, "greedy_argmax_roofline") is None


def test_breakdown(tick_run):
    b = tick_run.trace.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    idle = dict(b["idle_gaps"])
    # gaps [0, 50) under PjitFunction(x), [400, 550) and [900, 1000)
    assert idle["PjitFunction(x)"] == pytest.approx(50e-9)
    assert idle["bench.tick"] == pytest.approx(250e-9)
