"""``bench/trace_detail.py``: host events from the line that holds the
window, idle by host span, the greedy's phases, on traces built by hand
and on the traces recorded on the chip (``data/greedy_tick.*``,
``data/sweep4.xplane.pb``)."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import greedy_phases as gp
from bench import harness
from bench import trace_detail as td
from bench import trace_reduce as tr
from bench.trace_reduce import Device, Events, TraceSummary

DATA = Path(__file__).with_name("data")


def _pd(lines):
    """A ``ProfileData`` stand-in: one device plane and a host plane with
    ``lines`` (``{name: [(start, dur, event name)]}``)."""
    ev = lambda rows: [NS(start_ns=a, duration_ns=d, name=n)  # noqa: E731
                       for a, d, n in rows]
    host = NS(name=tr.HOST_PLANE,
              lines=[NS(name=k, events=ev(v)) for k, v in lines])
    return NS(planes=[NS(name="/device:TPU:0", lines=[]), host])


def _summary():
    """One device, busy [100, 400) and [600, 900) of a [0, 1000) window:
    idle gaps at 0, 400 and 900."""
    mods = Events.make([(100, 300, "jit_run(1)"), (600, 300, "jit_run(1)")])
    return TraceSummary(devices=[Device(0, mods, Events.make([]))],
                        host=Events.make([]), window=(0, 1000))


SPANS = [(0, 1000, "bench.window"), (0, 500, "bench.tick"),
         (500, 500, "bench.tick"), (390, 40, "placement.as_jax")]


@pytest.mark.parametrize("lines", [
    # the main thread's line under ``python3``: not the reduction's name
    [("python3", SPANS), ("pjrt-tpu-tasks/1", [(0, 1000, "Execute")])],
    # two lines named ``python``; the spans on the first
    [("python", SPANS), ("python", [(0, 1000, "Execute")])],
], ids=["python3", "two-python-lines"])
def test_gaps_labelled_from_the_line_that_holds_the_window(lines):
    pd = _pd(lines)
    s = td.with_host(_summary(), pd)
    assert s.breakdown()["idle_gaps"] == [["bench.tick", 2e-7],
                                          ["placement.as_jax", 2e-7]]
    assert td.idle_by_span(s) == pytest.approx(
        {"bench.tick": 2e-7, "placement.as_jax": 2e-7})
    held = [(d["line"], d["events"]) for d in td.host_lines(pd)
            if d["window"]]
    assert held == [(lines[0][0], len(SPANS))]


def test_idle_during_splits_a_gap_between_spans():
    pd = _pd([("python3", [(0, 1000, "bench.window"),
                           (0, 100, "sweep.materialize"),
                           (380, 100, "sweep.pad"),
                           (400, 30, "DevicePut"),
                           (480, 150, "sweep.dispatch"),
                           (850, 100, "sweep.wait")])])
    s = td.with_host(_summary(), pd)
    # gaps [0, 100), [400, 600), [900, 1000); the second begins in the
    # pad's copy and ends in the dispatch, the third ends after the wait
    assert td.idle_by_span(s) == pytest.approx(
        {"sweep.materialize": 1e-7, "DevicePut": 2e-7, "sweep.wait": 1e-7})
    assert td.idle_during(s) == pytest.approx(
        {"sweep.materialize": 1e-7, "DevicePut": 3e-8, "sweep.pad": 5e-8,
         "sweep.dispatch": 1.2e-7, "sweep.wait": 5e-8, "bench.window": 5e-8})
    assert td.idle_during(td.phases_only(s)) == pytest.approx(
        {"sweep.materialize": 1e-7, "sweep.pad": 8e-8,
         "sweep.dispatch": 1.2e-7, "sweep.wait": 5e-8, "bench.window": 5e-8})
    assert td.input_idle_pct(s) == pytest.approx(18.0)
    assert td.idle_s(s) == pytest.approx([4e-7])


def test_timeline_outside_every_span_and_past_a_parent():
    host = Events.make([(10, 20, "a"), (15, 30, "b"), (50, 10, "c")])
    bounds, names = td.timeline(host)
    # b outlasts a and is cut at a's end
    assert bounds.tolist() == [10, 15, 30, 50, 60]
    assert names == ["a", "b", td.NO_SPAN, "c", td.NO_SPAN]


def test_recorded_tick_idle_has_a_span():
    """The tick recorded on one v5e: its main thread's line is
    ``python3``, where the reduction finds nothing; read from the line
    that holds the window, no idle is left without a span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "greedy_tick.xplane.pb"))
    s = tr.read(DATA / "greedy_tick.xplane.pb")
    assert len(s.host) == 0
    assert [k for k, _ in s.breakdown()["idle_gaps"]] == ["(no host span)"]
    fixed = td.with_host(s, pd)
    by = td.idle_by_span(fixed)
    assert sum(by.values()) == pytest.approx(td.idle_s(fixed)[0])
    assert by.get("(no host span)", 0.0) <= 0.05 * sum(by.values())
    assert any(k.startswith("placement.") for k in by)
    # by the program's own spans: the tick's host phases hold most of it
    phase = td.idle_during(td.phases_only(fixed))
    assert set(phase) <= {"bench.window", "bench.tick", "placement.as_jax",
                          "placement.candidates", "placement.greedy",
                          "placement.wait"}
    assert sum(v for k, v in phase.items() if k.startswith("placement.")) \
        > 0.9 * sum(phase.values())


def test_recorded_sweep_idle_by_span():
    """The small chunk recorded on four v5e chips: every chip's idle is
    filed under the chunk's spans or what JAX did inside them."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "sweep4.xplane.pb"))
    s = td.with_host(tr.read(DATA / "sweep4.xplane.pb"), pd)
    assert len(td.idle_s(s)) == 4
    by = td.idle_by_span(s)
    assert sum(by.values()) == pytest.approx(
        sum(td.idle_s(s)) / 4, rel=1e-9)
    assert by.get("(no host span)", 0.0) <= 0.05 * sum(by.values())
    assert "sweep.pad" in by
    phase = td.idle_during(td.phases_only(s))
    assert {"sweep.pad", "sweep.dispatch", "sweep.wait"} <= set(phase)
    assert sum(phase.values()) == pytest.approx(sum(by.values()))
    assert td.input_idle_pct(s) == pytest.approx(
        100 * (phase["sweep.pad"] + phase["sweep.materialize"])
        / s.window_s())


def test_kept_trace_read_offline(capsys):
    assert td.main(["--xplane", str(DATA / "sweep4.xplane.pb")]) == 0
    out = json.loads(capsys.readouterr().out)["trace_detail"]
    assert len(out["idle_s"]) == 4 and "greedy" not in out
    assert out["idle_during_phase"].get(td.NO_SPAN, 0.0) == 0.0


def test_recorded_greedy_detail(manifest, monkeypatch):
    summary = tr.read(DATA / "greedy_tick.xplane.pb",
                      DATA / "greedy_tick_perfetto.json.gz")
    doc = json.loads((DATA / "greedy_tick_facts.json").read_text())
    with gzip.open(DATA / "greedy_tick_hlo.txt.gz", "rt") as f:
        text = f.read()
    monkeypatch.setattr(gp, "program_text", lambda facts: text)
    run = harness.Run(cell=harness.load_cell(manifest, "metro-tick"),
                      setup_s=0.0, window_s=summary.window_s(), steps=1,
                      items=1, facts=dict(doc["facts"], ticks=1),
                      trace=summary, peaks={"hbm_bytes_per_s": 819e9})
    d = td.greedy_detail(run)
    assert set(d["phases_s"]) == set(gp.PHASES)
    assert sum(d["phases_s"].values()) == pytest.approx(
        d["cover_of_leaves"] * d["leaf_s"])
    assert d["cover_of_program"] >= 0.95
    assert d["leaf_s"] <= d["program_s"]
    assert all(s < 0.01 * d["program_s"] for _, s in d["outside_s"])


def test_main_needs_a_tpu_and_restores_the_reduction():
    read, read_metrics = tr.read, harness.read_metrics
    assert td.main(["--workload", "metro-tick", "--seed", "1",
                    "--seconds", "1"]) == 1
    assert tr.read is read and harness.read_metrics is read_metrics
