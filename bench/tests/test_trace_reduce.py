"""The trace reduction on a small trace recorded on one TPU v5e: one
sparse tick (2000 users, 4 edges, 20 services) with the Pallas kernels,
inside the harness's ``bench.window`` and ``bench.tick`` spans
(``data/small.xplane.pb`` and the Perfetto JSON the profiler wrote
beside it)."""
from collections import Counter
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).with_name("data")


@pytest.fixture(scope="module")
def summary():
    return tr.read(DATA / "small.xplane.pb", DATA / "small_perfetto.json.gz")


def test_planes_and_window(summary):
    assert [d.index for d in summary.devices] == [0]
    lo, hi = summary.window
    assert hi - lo == 34986797          # the bench.window span, ns
    assert 0 < summary.busy_s() < summary.window_s()
    assert 0.0 < summary.idle_share() < 1.0


def test_programs(summary):
    dev = summary.devices[0]
    run = summary.programs(dev, r"jit_run\b")
    assert len(run) == 1 and int(run.dur[0]) == 5641497
    cand = summary.programs(dev, r"jit_qos_candidates\b")
    assert len(cand) == 1
    # one program ran before the window opened; it is left out
    lo, _ = summary.window
    assert len(summary.programs(dev, ".")) == (dev.modules.start >= lo).sum()
    assert len(dev.modules) == 72


def test_ops_lie_inside_their_programs(summary):
    """Busy time is read from program executions; the ops say the same."""
    dev = summary.devices[0]
    lo, hi = summary.window
    by_ops = tr.union_ns(dev.ops.start, dev.ops.end, lo, hi)
    by_programs = summary.busy_ns(dev)
    assert 0 < by_ops <= by_programs
    assert by_ops > 0.9 * by_programs


def test_scopes(summary):
    dev = summary.devices[0]
    kernel = summary.scope_ops(dev, ["greedy_argmax_pallas"])
    # one pallas_call and its input pads per greedy iteration
    calls = [n for n, s in zip(kernel.name, kernel.scope)
             if s.endswith("pallas_call:")]
    assert len(calls) == 97
    assert max(Counter(kernel.name).values()) == 97
    cand = summary.scope_ops(dev, ["qos_candidates_pallas",
                                   "qos_candidates_ref"])
    assert any(s.endswith("pallas_call:") for s in cand.scope)
    run = summary.programs(dev, r"jit_run\b")
    assert kernel.end.max() <= run.end.max()
    assert kernel.dur.sum() < run.dur.sum()


def test_breakdown(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
    # leaves only: the loop itself is not listed beside its body
    assert not any(k.split(" ")[-1].startswith("while.")
                   for k, _ in b["device_ops"])
    idle = sum(v for _, v in b["idle_gaps"])
    assert idle <= summary.window_s() - summary.busy_s() + 1e-9


def test_without_ops(summary):
    bare = tr.read(DATA / "small.xplane.pb")
    assert not len(bare.devices[0].ops)
    assert bare.busy_s() == summary.busy_s()
    assert bare.breakdown()["device_ops"][0][0] == "jit_run"
