"""The greedy layer's readers (``bench/greedy_phases.py`` and the metrics
``pick_ms.tick``, ``rescore_ms.tick``, ``stop_test_ms.tick``,
``greedy_iters.tick``, ``rescore_roofline``) on a compiled program and a
trace built by hand, so that every number can be worked out on paper."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import greedy_phases as gp
from bench import harness
from bench import trace_reduce as tr
from bench.trace_reduce import Device, Events, TraceSummary
from repro import obs

# The shape of the v5e's compiled sparse tick, cut down: a pair scatter the
# compiler made into a custom fusion with no metadata, fed by a sort with
# none, inside the re-score's cond branch; a loop it built with a body
# without metadata; a gather that keeps its own op_name.
HLO = """HloModule jit_run, entry_computation_layout={()->f32[]}

%fused_computation.7.clone (param_0: f32[8], param_1: s32[16], param_2: f32[16]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %param_1 = s32[16]{0} parameter(1)
  %param_2 = f32[16]{0} parameter(2)
  %reshape.1 = f32[16]{0} reshape(%param_2), metadata={op_name="jit(run)/while/body/greedy.rescore/cond/branch_1_fun/select_n" stack_frame_id=13}
  ROOT %scatter.25 = f32[8]{0} scatter(%param_0, %param_1, %reshape.1), to_apply=%add_f32
}

%add_f32 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="scatter-add"}
  %b = f32[] parameter(1), metadata={op_name="scatter-add"}
  ROOT %add.1 = f32[] add(%a, %b)
}

%compare (p0: s32[], p1: s32[]) -> pred[] {
  %p0 = s32[] parameter(0)
  %p1 = s32[] parameter(1)
  ROOT %lt = pred[] compare(%p0, %p1), direction=LT
}

%wide.body (w: (u32[], f32[16])) -> (u32[], f32[16]) {
  %w = (u32[], f32[16]{0}) parameter(0)
  %gte.w = f32[16]{0} get-tuple-element(%w), index=1
  ROOT %tuple.w = (u32[], f32[16]{0}) tuple(%gte.w, %gte.w)
}

%branch_1 (arg: (s32[16], f32[16])) -> f32[8] {
  %arg = (s32[16]{0}, f32[16]{0}) parameter(0)
  %gte.0 = s32[16]{0} get-tuple-element(%arg), index=0
  %while.42 = (u32[], f32[16]{0}) while(%arg), condition=%compare, body=%wide.body
  %gte.402 = f32[16]{0} get-tuple-element(%while.42), index=1, metadata={op_name="jit(run)/while/body/greedy.rescore/cond/branch_1_fun/select_n" stack_frame_id=13}
  %iota.5 = s32[16]{0} iota(), iota_dimension=0
  %sort = (s32[16]{0}, f32[16]{0}) sort(%gte.0, %gte.402), dimensions={0}, to_apply=%compare
  %gte.383 = s32[16]{0} get-tuple-element(%sort), index=0
  %gte.384 = f32[16]{0} get-tuple-element(%sort), index=1
  %constant.35 = f32[] constant(0)
  %copy-start.3 = (f32[16]{0}, f32[16]{0}, u32[]) copy-start(%gte.402)
  ROOT %fusion.7 = f32[8]{0} fusion(%gte.383, %gte.384, %constant.35), kind=kCustom, calls=%fused_computation.7.clone
}

ENTRY %main (idx: s32[16], q: f32[16]) -> f32[8] {
  %idx = s32[16]{0} parameter(0), metadata={op_name="cand_idx"}
  %q = f32[16]{0} parameter(1), metadata={op_name="cand_q"}
  %gather.1 = s32[16]{0} gather(%idx, %idx), metadata={op_name="jit(run)/while/body/greedy.pick/gather" stack_frame_id=24}
  %tuple.1 = (s32[16]{0}, f32[16]{0}) tuple(%gather.1, %q)
  ROOT %cond.19 = f32[8]{0} conditional(%idx, %tuple.1), branch_computations={%branch_1}, metadata={op_name="jit(run)/while/body/greedy.rescore/cond" stack_frame_id=24}
}
"""

RESCORE = "jit(run)/while/body/greedy.rescore/cond/branch_1_fun/select_n"


def test_scope_map_fills_what_the_compiler_left_bare():
    m = gp.scope_map(HLO)
    assert m["gather.1"] == "jit(run)/while/body/greedy.pick/gather"
    # a fusion without metadata: the op_name of what is fused into it
    assert m["fusion.7"] == RESCORE
    # ops without metadata: their nearest consumer's scope ...
    assert m["sort"] == RESCORE and m["gte.383"] == RESCORE
    assert m["while.42"] == RESCORE
    # ... and none where no consumer has one: the body of a loop the
    # compiler built, an op nothing reads
    assert not {"gte.w", "tuple.w", "iota.5", "copy-start.3"} & set(m)


def test_scope_map_of_a_compiled_tick():
    """On a program compiled here: every phase named, every instruction
    with a scope path of its own keeps it."""
    import re

    from repro.core.candidates import impl_table_np
    from repro.core.instance import synthetic_instance
    from repro.kernels.qos_matrix.ops import qos_candidates_from_instance
    from repro.workloads.batched import sparse_evaluator

    inst = synthetic_instance(300, n_edges=3, n_services=15, seed=1)
    ji = inst.as_jax()
    ci, cq = qos_candidates_from_instance(
        ji, impl_table_np(inst.sm_service, inst.S), None, use_kernel=False)
    text = sparse_evaluator(inst.P + 1, False).lower(
        ci, cq, ji.u_edge, ji.sm_service, ji.sm_r, ji.R).compile().as_text()
    m = gp.scope_map(text)
    for phase in gp.PHASES:
        assert any(f"/{phase}/" in s for s in m.values()), phase
    own = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*op_name=\"(jit[^\"]*)\"",
                     text, re.M)
    assert own and all(m[n] == s for n, s in own)


def _events(rows):
    return Events.make(rows)


@pytest.fixture
def greedy_run(manifest, monkeypatch):
    """One tick in a 1000-ns window: ``jit_run`` at [100, 900), its ops:
    a pick gather (scoped, 100 ns), the re-score's bare fusion and sort
    (200 + 100 ns), its cond container (holds them), an unknown bare op
    (50 ns) and a stop-test op (150 ns); a candidate-build op outside."""
    mods = _events([(50, 50, "jit_qos_candidates(1)"),
                    (100, 800, "jit_run(2)")])
    ops = _events([
        (60, 30, "fusion.3", "jit(qos_candidates)/qos_candidates_pallas/x:"),
        (100, 100, "gather.1", "jit(run)/while/body/greedy.pick/gather:"),
        (200, 400, "cond.19", "jit(run)/while/body/greedy.rescore/cond:"),
        (200, 100, "sort", ""),
        (300, 200, "fusion.7", ""),
        (600, 50, "copy.9", ""),
        (650, 20, "copy-start.3", "jit(run)/while:"),
        (700, 150, "and.2", "jit(run)/while/body/greedy.stop_test/and:"),
    ])
    host = _events([(0, 1000, "bench.window"), (0, 1000, "bench.tick")])
    trace = TraceSummary(devices=[Device(0, mods, ops)], host=host,
                         window=(0, 1000))
    cell = harness.load_cell(manifest, "metro-tick")
    run = harness.Run(cell=cell, setup_s=1.0, window_s=1e-6, steps=1,
                      items=1, facts={"users": 1000, "max_impls": 10,
                                      "edges": 4, "impls": 25, "ticks": 1},
                      trace=trace, peaks={"hbm_bytes_per_s": 819e9})
    texts = []
    monkeypatch.setattr(gp, "program_text",
                        lambda facts: texts.append(facts) or HLO)
    tr = obs.enable()
    tr.count("placement.greedy_iters", 7)
    tr.count("placement.greedy_rescores", 3)
    obs.disable()
    run.texts = texts
    return run


def read(run, name):
    return run.cell.reader(name).read(run)


def test_phase_readers(greedy_run):
    # leaves only: the cond container is dropped, its fusion and sort are
    # filled from the map; copy.9 has no entry and stays unattributed, and
    # copy-start.3 keeps the loop's scope the profiler gave it
    assert read(greedy_run, "pick_ms.tick") == pytest.approx(100e-6)
    assert read(greedy_run, "rescore_ms.tick") == pytest.approx(300e-6)
    assert read(greedy_run, "stop_test_ms.tick") == pytest.approx(150e-6)
    assert read(greedy_run, "greedy_iters.tick") == 7
    want = 100 * (3 * (8 * 10 * 1000 + 10 * 1000 + 4 * 4 * 26 + 8 * 4 * 25)
                  / 819e9) / 300e-9
    assert read(greedy_run, "rescore_roofline") == pytest.approx(want)
    # the program is compiled once for all the readers of one trace
    assert len(greedy_run.texts) == 1


def test_phase_readers_silent_on_a_program_without_scopes(greedy_run):
    """A program without the ``greedy.*`` tags (the parent's): nothing is
    compiled and nothing is read."""
    d = greedy_run.trace.devices[0]
    d.ops = Events(d.ops.start, d.ops.dur, d.ops.name,
                   [s.replace("greedy.", "g_") for s in d.ops.scope])
    for name in ("pick_ms.tick", "rescore_ms.tick", "stop_test_ms.tick",
                 "rescore_roofline"):
        assert read(greedy_run, name) is None
    assert greedy_run.texts == []


def test_map_that_disagrees_with_the_trace_is_not_used(greedy_run):
    d = greedy_run.trace.devices[0]
    d.ops.scope[1] = "jit(run)/while/body/greedy.stop_test/gather:"
    assert read(greedy_run, "rescore_ms.tick") is None


def test_counters_need_the_program_and_the_trace(greedy_run, monkeypatch):
    monkeypatch.setattr(obs, "last_tracer", lambda: None)
    assert read(greedy_run, "greedy_iters.tick") is None
    assert read(greedy_run, "rescore_roofline") is None
    monkeypatch.delattr(obs, "last_tracer")        # a program without it
    assert gp.counter("placement.greedy_iters") is None


# -- a trace recorded on one TPU v5e ------------------------------------------

DATA = Path(__file__).with_name("data")


@pytest.fixture(scope="module")
def recorded(manifest):
    """One sparse tick (2000 users, 4 edges, 20 services, the Pallas
    kernels) inside ``bench.window``/``bench.tick`` spans, recorded with
    the program's phase scopes: its trace, the compiled text of its greedy
    program and the obs counters of the tick."""
    summary = tr.read(DATA / "greedy_tick.xplane.pb",
                      DATA / "greedy_tick_perfetto.json.gz")
    doc = json.loads((DATA / "greedy_tick_facts.json").read_text())
    with gzip.open(DATA / "greedy_tick_hlo.txt.gz", "rt") as f:
        text = f.read()
    cell = harness.load_cell(manifest, "metro-tick")
    run = harness.Run(cell=cell, setup_s=0.0, window_s=summary.window_s(),
                      steps=1, items=1, facts=dict(doc["facts"], ticks=1),
                      trace=summary, peaks={"hbm_bytes_per_s": 819e9})
    return run, text, doc["counters"]


@pytest.fixture
def recorded_run(recorded, monkeypatch):
    run, text, counters = recorded
    monkeypatch.setattr(gp, "program_text", lambda facts: text)
    tr_ = obs.enable()
    for name, n in counters.items():
        tr_.count(name, n)
    obs.disable()
    return run


def test_recorded_trace_leaves_the_big_ops_bare():
    """Why the map exists: on the chip the pair scatters reach the trace
    with no scope."""
    summary = tr.read(DATA / "greedy_tick.xplane.pb",
                      DATA / "greedy_tick_perfetto.json.gz")
    dev = summary.devices[0]
    ops = dev.ops.select(summary.in_window(dev.ops))
    bare = {n for n, s in zip(ops.name, ops.scope) if not s}
    assert {"fusion.24", "fusion.26", "cond.19"} <= bare


def test_recorded_phases_cover_the_greedy(recorded_run):
    ops = gp.greedy_ops(recorded_run)
    total = int(ops.dur.sum())
    program = int(recorded_run.trace.programs(recorded_run.trace.devices[0],
                                              gp.PROGRAM).dur.sum())
    assert 0.9 * program < total <= program
    phase = np.array([any(f"/{p}/" in s for p in gp.PHASES)
                      for s in ops.scope])
    assert ops.dur[phase].sum() >= 0.95 * total
    # no op of the program with more than 1 % of its time is left out
    by_name = {}
    for n, s, d in zip(ops.name, ops.scope, ops.dur.tolist()):
        if not any(f"/{p}/" in s for p in gp.PHASES):
            by_name[n] = by_name.get(n, 0) + d
    assert max(by_name.values()) < 0.01 * total
    for p in gp.PHASES:
        assert any(f"/{p}/" in s for s in ops.scope), p


def test_recorded_readers(recorded_run):
    ms = {name: read(recorded_run, name)
          for name in ("pick_ms.tick", "rescore_ms.tick", "stop_test_ms.tick",
                       "greedy_ms.tick", "greedy_iters.tick",
                       "rescore_roofline")}
    assert all(v is not None and v > 0 for v in ms.values()), ms
    phases = ms["pick_ms.tick"] + ms["rescore_ms.tick"] \
        + ms["stop_test_ms.tick"]
    assert phases < ms["greedy_ms.tick"]
    assert ms["greedy_iters.tick"] == 127
    assert 0 < ms["rescore_roofline"] < 100


def test_recorded_sweep_chunk_spans():
    """One small sweep chunk recorded on four v5e chips: the chunk's host
    phases reach the profiler, nested in ``sweep.chunk``, on the host line
    that holds ``bench.window``. That line is named after the process's
    main thread, ``python3`` under the benchmark's command."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "sweep4.xplane.pb"))
    host = next(p for p in pd.planes if p.name == tr.HOST_PLANE)
    lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events] for line in host.lines}
    holders = [n for n, ev in lines.items()
               if any(e[2] == tr.WINDOW_SPAN for e in ev)]
    assert holders == ["python3"]
    ev = lines["python3"]
    chunks = [e for e in ev if e[2] == "sweep.chunk"]
    for name in ("sweep.pad", "sweep.dispatch", "sweep.wait"):
        mine = [e for e in ev if e[2] == name]
        assert mine and all(any(c[0] <= e[0] and e[1] <= c[1] for c in chunks)
                            for e in mine), name
    # the sharded call slices each input across the chips as it dispatches
    dispatch = [e for e in ev if e[2] == "sweep.dispatch"]
    slices = [e for e in ev if e[2] == "PjitFunction(_multi_slice)"]
    assert slices and all(any(d[0] <= e[0] and e[1] <= d[1] for d in dispatch)
                          for e in slices)
    summary = tr.read(DATA / "sweep4.xplane.pb")
    assert [d.index for d in summary.devices] == [0, 1, 2, 3]
    assert all(len(d.modules) for d in summary.devices)
