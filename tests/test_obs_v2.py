"""repro.obs v2 — wire-protocol goldens and rejection cases, histogram /
registry merge parity, the stream-on byte-identity invariant (with a live
dashboard attached), fleet trace stitching across subprocess workers,
SLO evaluation + burn rates, the benchmark regression gate, and the
dash / ``fleet status --watch`` smoke."""
import io
import json
import math
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.obs.aggregate import (rollup_counters, rollup_metrics,
                                 stitch_fleet, stitch_traces,
                                 telemetry_anchors)
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.slo import SLO, compare_bench, evaluate_slos, load_slos
from repro.obs.stream import (FileSink, FrameValidator, SocketSink,
                              StreamError, StreamPublisher,
                              parse_stream_spec, read_stream)

SRC = Path(__file__).resolve().parents[1] / "src"

#: Shrunk scenario (see tests/test_horizon.py) — keeps horizons fast.
SMALL = {"n_user_slots": 32, "n_services": 8, "max_impls": 3, "n_edges": 4}


@pytest.fixture(autouse=True)
def _obs_off():
    """Tracing and streaming must never leak between tests."""
    assert not obs.enabled() and not obs.stream_active()
    yield
    obs.disable()
    obs.disable_stream()


def _fake_clock(step=1.0, start=100.0):
    state = {"t": start - step}

    def clock():
        state["t"] += step
        return state["t"]

    return clock


def _spec():
    from repro.sweeps import SweepSpec
    grid = (tuple(sorted({**SMALL, "switching_cost": 0.0,
                          "stickiness": 0.0}.items())),)
    return SweepSpec(kind="serving", scenarios=("steady",), seeds=(0, 1),
                     n_ticks=2, algos=("edf",), override_grid=grid)


# ===========================================================================
# Wire protocol: golden frames, handshake, rejection cases
# ===========================================================================

def test_stream_file_golden_lines(tmp_path):
    """The exact bytes on the wire, via the injectable clock."""
    path = tmp_path / "s.jsonl"
    pub = StreamPublisher(FileSink(path), source="test",
                          clock=_fake_clock(step=1.0, start=100.0))
    pub.emit("tick", {"tick": 0, "queue_depth": 3})
    pub.close()
    lines = path.read_text().strip().splitlines()
    assert [json.loads(line) for line in lines] == [
        {"payload": {"pid": os.getpid(), "source": "test",
                     "stream_schema": 1},
         "seq": 0, "stream_schema": 1, "t": 100.0, "type": "hello"},
        {"payload": {"queue_depth": 3, "tick": 0},
         "seq": 1, "stream_schema": 1, "t": 101.0, "type": "tick"},
        {"payload": {"n_frames": 2},
         "seq": 2, "stream_schema": 1, "t": 102.0, "type": "bye"},
    ]
    # and keys are sorted on the wire (stable goldens, diffable streams)
    assert all(line.index('"payload"') < line.index('"seq"')
               < line.index('"type"') for line in lines)


def test_read_stream_roundtrip_and_partial_tail(tmp_path):
    path = tmp_path / "s.jsonl"
    pub = StreamPublisher(FileSink(path), source="rt")
    pub.emit("tick", {"tick": 0})
    # an incomplete trailing line must be buffered, never parsed
    with open(path, "a") as f:
        f.write('{"stream_schema": 1, "seq": 2, "t": 1.0, "type": "ti')
    frames = list(read_stream(str(path), follow=False))
    assert [f["type"] for f in frames] == ["hello", "tick"]


def test_validator_rejects_missing_handshake():
    v = FrameValidator()
    with pytest.raises(StreamError, match="hello handshake"):
        v.feed({"stream_schema": 1, "seq": 0, "type": "tick",
                "payload": {}})


def test_validator_rejects_schema_mismatch():
    v = FrameValidator()
    with pytest.raises(StreamError, match="schema v99"):
        v.feed({"seq": 0, "type": "hello",
                "payload": {"stream_schema": 99}})


def test_validator_rejects_out_of_order_and_gaps():
    def hello(seq=0):
        return {"seq": seq, "type": "hello",
                "payload": {"stream_schema": 1}}

    v = FrameValidator()
    v.feed(hello())
    v.feed({"seq": 1, "type": "tick", "payload": {}})
    with pytest.raises(StreamError, match="out-of-order"):
        v.feed({"seq": 1, "type": "tick", "payload": {}})
    # contiguous mode (single-writer files): a gap is a lost frame
    v2 = FrameValidator(contiguous=True)
    v2.feed(hello())
    with pytest.raises(StreamError, match="missing frame"):
        v2.feed({"seq": 5, "type": "tick", "payload": {}})
    # socket mode tolerates gaps (broadcast drops frames for slow clients)
    v3 = FrameValidator(contiguous=False)
    v3.feed(hello())
    assert v3.feed({"seq": 5, "type": "tick", "payload": {}})["seq"] == 5


def test_validator_rejects_torn_complete_line(tmp_path):
    path = tmp_path / "s.jsonl"
    pub = StreamPublisher(FileSink(path), source="torn")
    pub.close()
    with open(path, "a") as f:
        f.write('{"seq": 3, "type": "tick", truncated-garbage}\n')
    with pytest.raises(StreamError, match="truncated/corrupt"):
        # bye at seq 1 terminates; feed the torn line directly instead
        v = FrameValidator()
        for line in path.read_text().splitlines():
            v.feed_line(line)


def test_parse_stream_spec():
    assert parse_stream_spec("1", "d.jsonl") == ("file", "d.jsonl")
    assert parse_stream_spec("true") == ("file", "obs_stream.jsonl")
    assert parse_stream_spec("unix:/tmp/x.sock") == ("unix", "/tmp/x.sock")
    assert parse_stream_spec("tcp:0.0.0.0:9000") == \
        ("tcp", ("0.0.0.0", 9000))
    assert parse_stream_spec("tcp:9000") == ("tcp", ("127.0.0.1", 9000))
    assert parse_stream_spec("/a/b.jsonl") == ("file", "/a/b.jsonl")


def test_socket_stream_replays_hello_to_late_joiner(tmp_path):
    sock = str(tmp_path / "s.sock")
    pub = StreamPublisher(SocketSink("unix", sock), source="sock")
    frames = []

    def reader():
        frames.extend(read_stream(f"unix:{sock}", timeout_s=5.0))

    th = threading.Thread(target=reader, daemon=True)
    th.start()
    deadline = time.monotonic() + 5.0
    while not pub._sink._clients and time.monotonic() < deadline:
        time.sleep(0.01)  # wait for the late joiner to be registered
    assert pub._sink._clients, "reader never connected"
    pub.emit("tick", {"tick": 7})
    pub.close()
    th.join(timeout=5.0)
    assert not th.is_alive()
    types = [f["type"] for f in frames]
    assert types[0] == "hello"          # replayed to the late joiner
    assert "tick" in types and types[-1] == "bye"
    assert not Path(sock).exists()      # close unlinks the unix path


def test_publisher_survives_sink_failure(tmp_path):
    path = tmp_path / "s.jsonl"
    pub = StreamPublisher(FileSink(path), source="fail")
    pub._sink._f.close()  # simulate the disk going away mid-run
    assert pub.emit("tick", {"tick": 0}) is False
    assert pub.failed and pub.emit("tick", {"tick": 1}) is False


def test_enable_stream_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_OBS_STREAM", raising=False)
    assert obs.enable_stream_from_env() is None
    monkeypatch.setenv("REPRO_OBS_STREAM", "off")
    assert obs.enable_stream_from_env() is None
    path = tmp_path / "env.jsonl"
    monkeypatch.setenv("REPRO_OBS_STREAM", "1")
    pub = obs.enable_stream_from_env(default_path=str(path), source="env")
    assert pub is not None and obs.stream_active()
    obs.publish("tick", tick=0)
    obs.disable_stream()
    assert not obs.stream_active()
    types = [f["type"] for f in read_stream(str(path))]
    assert types == ["hello", "tick", "bye"]


# ===========================================================================
# Histogram / registry merge: exact bucket arithmetic
# ===========================================================================

def test_histogram_merge_parity_with_concatenated_samples():
    rng = np.random.default_rng(3)
    a = rng.lognormal(mean=-3.0, sigma=1.0, size=5_000)
    b = rng.lognormal(mean=-1.0, sigma=0.5, size=3_000)
    ha, hb, hall = Histogram(), Histogram(), Histogram()
    ha.observe_many(a)
    hb.observe_many(b)
    hall.observe_many(np.concatenate([a, b]))
    merged = ha.merge(hb)
    # bucket counts, count, min, max: exactly the single-pass histogram
    assert merged._buckets == hall._buckets
    assert merged.count == hall.count
    assert merged.min == hall.min and merged.max == hall.max
    # float sum differs only by addition-order ulps
    np.testing.assert_allclose(merged.sum, hall.sum, rtol=1e-12)
    for q in (0.5, 0.95, 0.99):
        assert merged.quantile(q) == hall.quantile(q)


def test_histogram_merge_rejects_layout_mismatch():
    h1, h2 = Histogram(), Histogram(growth=2.0)
    with pytest.raises(ValueError, match="bucket layouts"):
        h1.merge(h2)


def test_histogram_record_roundtrip():
    h = Histogram()
    h.observe_many([0.001, 0.01, 0.1, 0.1])
    back = Histogram.from_record(json.loads(json.dumps(h.record())))
    assert back._buckets == h._buckets and back.count == h.count
    assert back.min == h.min and back.max == h.max and back.sum == h.sum


def test_registry_merge_and_from_snapshot():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("items", executor="serving").inc(4)
    r2.counter("items", executor="serving").inc(6)
    r2.counter("items", executor="host").inc(1)
    r1.gauge("qos").set(0.5)
    r2.gauge("qos").set(0.9)
    r1.histogram("lat", scenario="steady").observe_many([0.01, 0.02])
    r2.histogram("lat", scenario="steady").observe_many([0.04])
    merged = MetricsRegistry().merge(r1).merge(r2)
    assert merged.counter("items", executor="serving").value == 10
    assert merged.counter("items", executor="host").value == 1
    assert merged.gauge("qos").value == 0.9      # last writer in order
    assert merged.histogram("lat", scenario="steady").count == 3
    # snapshot → from_snapshot is the identity on the snapshot
    snap = merged.snapshot()
    assert MetricsRegistry.from_snapshot(snap).snapshot() == snap
    with pytest.raises(ValueError, match="schema v9"):
        MetricsRegistry.from_snapshot([{"metrics_schema": 9,
                                        "kind": "counter", "name": "x"}])


# ===========================================================================
# Trace stitching: pid swimlanes, clock alignment, rollups
# ===========================================================================

def _worker_doc(pid, wall_ns, n=1):
    tr = obs.Tracer(capacity=16,
                    clock=_fake_clock(step=1000, start=1000))
    for _ in range(n):
        with tr.span("tick.place"):
            pass
    tr.count("served", 2)
    tr.metrics.histogram("serving.latency_s").observe_many([0.01, 0.02])
    doc = tr.snapshot()
    doc["pid"] = pid
    if wall_ns is None:
        doc.pop("anchor", None)
    else:
        doc["anchor"] = {"wall_ns": wall_ns, "mono_ns": 0}
    return doc


def test_stitch_traces_aligns_monotonic_clocks():
    # worker A's clock is offset +10µs on the shared wall timeline
    a = _worker_doc(pid=1, wall_ns=10_000)
    b = _worker_doc(pid=2, wall_ns=0)
    chrome = stitch_traces([a, b], labels=["wa", "wb"])
    assert obs.validate_chrome_trace(chrome) == 2
    assert chrome["otherData"]["stitched_from"] == {"wa": 1, "wb": 2}
    assert chrome["otherData"]["counters"] == {"served": 4}
    x = {ev["pid"]: ev for ev in chrome["traceEvents"]
         if ev["ph"] == "X"}
    assert x[2]["ts"] == 0.0            # earliest aligned record at t=0
    assert x[1]["ts"] == 10.0           # shifted by the anchor delta (µs)
    names = {ev["pid"]: ev["args"]["name"] for ev in chrome["traceEvents"]
             if ev.get("name") == "process_name"}
    assert names == {1: "wa", 2: "wb"}  # one swimlane per worker


def test_stitch_traces_remaps_pid_collisions_and_unanchored():
    a = _worker_doc(pid=7, wall_ns=5_000)
    b = _worker_doc(pid=7, wall_ns=None)   # pre-v2 artifact, no anchor
    chrome = stitch_traces([a, b], labels=["wa", "wb"])
    pids = set(chrome["otherData"]["stitched_from"].values())
    assert len(pids) == 2 and 7 in pids    # collision remapped, not merged
    # the unanchored artifact is start-aligned: its first record at ts=0
    b_pid = chrome["otherData"]["stitched_from"]["wb"]
    b_ts = [ev["ts"] for ev in chrome["traceEvents"]
            if ev["ph"] == "X" and ev["pid"] == b_pid]
    assert min(b_ts) == 0.0


def test_stitch_rollup_metrics_bucket_exact():
    docs = [_worker_doc(pid=1, wall_ns=0), _worker_doc(pid=2, wall_ns=0)]
    reg = rollup_metrics(docs)
    h = reg.histogram("serving.latency_s")
    assert h.count == 4 and h.min == 0.01 and h.max == 0.02
    assert rollup_counters(docs) == {"served": 4}


def test_telemetry_anchor_pairs(tmp_path):
    from repro.fleet.telemetry import WorkerTelemetry
    wt = WorkerTelemetry(tmp_path, "w0")
    wt.start()
    anchors = telemetry_anchors(tmp_path)
    assert os.getpid() in anchors
    wall_ns, mono_ns = anchors[os.getpid()]
    assert abs(wall_ns / 1e9 - time.time()) < 60.0
    assert 0 < mono_ns <= time.perf_counter_ns()


# ===========================================================================
# The hard invariant: streaming is observational only
# ===========================================================================

def test_serving_store_byte_identical_with_stream_and_dash(tmp_path,
                                                           monkeypatch):
    """REPRO_OBS_STREAM=1 + a live dashboard attached must not change one
    stored byte vs the stream-off run."""
    from repro.obs.dash import run_dash
    from repro.sweeps import SweepStore, run_sweep

    run_sweep(_spec(), store_dir=tmp_path / "off")

    stream = tmp_path / "stream.jsonl"
    monkeypatch.setenv("REPRO_OBS_STREAM", str(stream))
    obs.enable()
    obs.enable_stream_from_env(source="test")
    dash_out = io.StringIO()
    dash_rc = {}

    def _dash():
        dash_rc["rc"] = run_dash([str(stream)], interval=0.1,
                                 timeout_s=30.0, out=dash_out, clear=False)

    th = threading.Thread(target=_dash, daemon=True)
    th.start()
    run_sweep(_spec(), store_dir=tmp_path / "on")
    obs.disable()
    obs.disable_stream()        # bye frame ends the dashboard
    th.join(timeout=30.0)
    assert not th.is_alive() and dash_rc["rc"] == 0

    frames = list(read_stream(str(stream)))
    types = {f["type"] for f in frames}
    assert "tick" in types and "horizon" in types  # telemetry flowed
    assert "repro.obs dash" in dash_out.getvalue()

    off, on = SweepStore(tmp_path / "off"), SweepStore(tmp_path / "on")
    assert off.keys() == on.keys() and len(off) == 4
    for key in off.keys():
        a, b = np.float64(off.value(key)), np.float64(on.value(key))
        assert a.tobytes() == b.tobytes()
        ma, mb = off.metrics(key), on.metrics(key)
        assert ma.keys() == mb.keys()
        for name in ma:
            assert np.float64(ma[name]).tobytes() == \
                np.float64(mb[name]).tobytes(), (key, name)
    assert [c["keys"] for c in off.chunks()] == \
        [c["keys"] for c in on.chunks()]


def test_tick_reports_identical_with_stream_on(tmp_path):
    from repro.serving.horizon import HorizonConfig, run_horizon
    import dataclasses
    cfg = HorizonConfig(scenario="steady", policy="edf", seed=0, n_ticks=2,
                        overrides=tuple(sorted(SMALL.items())))
    ref = run_horizon(cfg)
    obs.enable_stream(str(tmp_path / "s.jsonl"), source="test")
    streamed = run_horizon(cfg)
    obs.disable_stream()
    np.testing.assert_array_equal(ref.tick_values(),
                                  streamed.tick_values())
    for a, b in zip(ref.per_tick, streamed.per_tick):
        assert repr(dataclasses.asdict(a)) == repr(dataclasses.asdict(b))


# ===========================================================================
# The acceptance run: 2 subprocess workers → one stitched trace
# ===========================================================================

def test_two_worker_fleet_stitches_into_one_trace(tmp_path, monkeypatch):
    from repro.fleet import plan
    from repro.fleet.cli import main as fleet_main
    from repro.fleet.worker import spawn_local_workers
    from repro.obs.cli import main as obs_main
    from repro.sweeps import run_sweep

    spec = _spec()              # 2 seeds → 2 tasks with seeds_per_task=1
    root = tmp_path / "fleet"
    plan(spec, root)
    monkeypatch.setenv("PYTHONPATH", str(SRC))
    monkeypatch.setenv("REPRO_OBS", "1")
    monkeypatch.setenv("REPRO_OBS_DIR", str(root / "obs"))
    monkeypatch.setenv("REPRO_OBS_STREAM", "1")
    # max_tasks=1 guarantees each worker executes exactly one task, so the
    # stitched trace must carry spans from two distinct pids
    procs = spawn_local_workers(root, 2, max_tasks=1)
    assert [p.wait(timeout=300) for p in procs] == [0, 0]

    out_path = tmp_path / "stitched_chrome.json"
    summary = stitch_fleet(root, out=out_path)
    chrome = summary["chrome_trace"]
    assert obs.validate_chrome_trace(chrome) >= 2
    assert summary["n_artifacts"] == 2 and len(summary["workers"]) == 2
    span_pids = {ev["pid"] for ev in chrome["traceEvents"]
                 if ev["ph"] == "X"}
    assert len(span_pids) == 2          # both workers, distinct swimlanes
    assert json.loads(out_path.read_text())["otherData"]["stitched_from"] \
        == summary["workers"]

    # fleet rollup == single-process run, exactly (bucket arithmetic):
    # serving latencies are deterministic simulation outputs, so the
    # merged per-worker histograms must equal the single-run histograms
    obs.enable()
    run_sweep(spec, store_dir=tmp_path / "single")
    tr = obs.disable()

    def _latency_records(snap):
        return sorted(
            ({k: r[k] for k in ("labels", "buckets", "count", "min",
                                "max")}
             for r in snap if r.get("kind") == "histogram"
             and r["name"] == "serving.latency_s"),
            key=lambda r: sorted(r["labels"].items()))

    assert _latency_records(summary["metrics"]) == \
        _latency_records(tr.metrics.snapshot())

    # per-worker streams landed, and both CLIs consume them: the stitch
    # CLI re-validates, dash --once renders at least one frame (exit 0),
    # and status --watch exits immediately on the drained queue
    streams = sorted((root / "stream").glob("*.jsonl"))
    assert len(streams) == 2
    assert obs_main(["stitch", "--root", str(root),
                     "--out", str(tmp_path / "cli_chrome.json"),
                     "--json", str(tmp_path / "cli_summary.json")]) == 0
    assert obs_main(["dash", "--root", str(root), "--once"]) == 0
    assert fleet_main(["status", "--root", str(root), "--watch",
                       "--interval", "0.01"]) == 0


# ===========================================================================
# SLOs: burn rates, spec files, the bench gate
# ===========================================================================

def _tick_frame(t, **payload):
    return {"stream_schema": 1, "seq": 0, "t": t, "type": "tick",
            "payload": payload}


def test_evaluate_slos_windows_and_burn_rates():
    frames = [_tick_frame(100.0 + i, miss_rate=0.2 + 0.2 * i,
                          queue_depth=10 * (i + 1)) for i in range(3)]
    slos = [SLO("miss", "tick.miss_rate", max_value=0.8),
            SLO("depth", "tick.queue_depth", max_value=20, agg="max"),
            SLO("qos", "tick.window_qos", min_value=0.5)]
    rep = {r.slo.name: r for r in evaluate_slos(slos, frames=frames)}
    assert rep["miss"].value == pytest.approx(0.4) and rep["miss"].ok
    assert rep["miss"].burn_rate == pytest.approx(0.4 / 0.8)
    assert rep["depth"].value == 30 and not rep["depth"].ok
    assert rep["depth"].burn_rate == pytest.approx(1.5)
    # no window_qos samples anywhere: vacuously ok, burn is NaN, n=0
    assert rep["qos"].ok and rep["qos"].n_samples == 0
    assert math.isnan(rep["qos"].burn_rate)
    # the sliding window drops old samples
    old = [_tick_frame(0.0, miss_rate=1.0)] + frames
    windowed = evaluate_slos([SLO("m", "tick.miss_rate", max_value=0.8,
                                  window_s=10.0)], frames=old)[0]
    assert windowed.n_samples == 3      # the t=0 frame fell out


def test_slo_hist_counter_bench_selectors():
    reg = MetricsRegistry()
    reg.histogram("serving.latency_s").observe_many([0.01] * 90 +
                                                    [10.0] * 10)
    bench = {"rows": [{"name": "obs_overhead", "us_per_call": 0.2,
                       "fields": {"disabled_pct": 0.5}}]}
    slos = [SLO("p99", "hist.serving.latency_s.p99", max_value=0.5),
            SLO("spans", "counter.n", min_value=1),
            SLO("ovh", "bench.obs_overhead.disabled_pct", max_value=3.0)]
    rep = {r.slo.name: r for r in
           evaluate_slos(slos, metrics=reg.snapshot(), counters={"n": 5},
                         bench=bench)}
    assert not rep["p99"].ok            # the 10s outlier is the p99
    assert rep["spans"].ok and rep["spans"].value == 5
    assert rep["ovh"].ok and rep["ovh"].burn_rate == \
        pytest.approx(0.5 / 3.0)
    with pytest.raises(ValueError, match="unknown metric selector"):
        evaluate_slos([SLO("x", "bogus.thing", max_value=1)])


def test_load_slos_version_checked(tmp_path):
    path = tmp_path / "slos.json"
    path.write_text(json.dumps({
        "slo_schema": 1,
        "slos": [{"name": "m", "metric": "tick.miss_rate",
                  "max_value": 0.5}]}))
    slos = load_slos(path)
    assert len(slos) == 1 and slos[0].max_value == 0.5
    path.write_text(json.dumps({"slo_schema": 99, "slos": []}))
    with pytest.raises(ValueError, match="schema v99"):
        load_slos(path)
    with pytest.raises(ValueError, match="exactly one"):
        SLO("bad", "tick.x", max_value=1, min_value=0)


def _bench_doc(**quality):
    return {"bench_schema": 1, "rows": [
        {"name": "serving_horizon", "us_per_call": 100.0,
         "fields": {"flash_qos_edf": quality.get("qos", 0.8),
                    "fit_us": quality.get("fit_us", 50.0)}}]}


def test_compare_bench_gate():
    base = _bench_doc()
    assert compare_bench(_bench_doc(), base)["violations"] == []
    # quality drift beyond tolerance fails in BOTH directions
    worse = compare_bench(_bench_doc(qos=0.5), base)
    better = compare_bench(_bench_doc(qos=0.99), base)
    assert worse["violations"] and better["violations"]
    # timing fields only fail past the slowdown factor
    slow = _bench_doc(fit_us=50.0 * 10)
    assert compare_bench(slow, base, max_slowdown=4.0)["violations"]
    assert compare_bench(slow, base, max_slowdown=20.0)["violations"] == []
    # us_per_call cliff
    cliff = _bench_doc()
    cliff["rows"][0]["us_per_call"] = 1e6
    assert any("us_per_call" in v for v in
               compare_bench(cliff, base)["violations"])
    # a requested row missing from either side is itself a violation
    res = compare_bench(_bench_doc(), base,
                        rows={"serving_horizon", "tuning_fit"})
    assert any("tuning_fit" in v for v in res["violations"])


def test_bench_cli_rows_compare_trajectory(tmp_path):
    """--rows gates row groups; --compare exits 0 on an identical baseline
    and 3 on an injected regression; --trajectory appends versioned
    records. Uses the instant roofline_table row."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the child must never take an accelerator
    repo = Path(__file__).resolve().parents[1]
    new_json = tmp_path / "new.json"
    traj = tmp_path / "traj.jsonl"

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "benchmarks.run",
             "--rows", "roofline_table", *extra],
            cwd=repo, env=env, capture_output=True, text=True, timeout=300)

    p = run("--json", str(new_json), "--trajectory", str(traj))
    assert p.returncode == 0, p.stderr
    doc = json.loads(new_json.read_text())
    assert [r["name"] for r in doc["rows"]] == ["roofline_table"]
    recs = [json.loads(line) for line in
            traj.read_text().strip().splitlines()]
    assert len(recs) == 1 and recs[0]["bench_traj_schema"] == 1
    assert recs[0]["rows"][0]["name"] == "roofline_table"

    # identical baseline → pass
    assert run("--compare", str(new_json)).returncode == 0
    # inject a quality regression into the baseline → exit 3
    bad = json.loads(new_json.read_text())
    fields = bad["rows"][0]["fields"]
    numeric = [k for k, v in fields.items()
               if isinstance(v, (int, float)) and not k.endswith(
                   ("_us", "_ns", "_ms", "_per_s", "_pct"))]
    assert numeric, fields
    fields[numeric[0]] = float(fields[numeric[0]]) + 10.0
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(bad))
    p = run("--compare", str(bad_path))
    assert p.returncode == 3 and "REGRESSION" in p.stderr
    # unknown row group is an argparse error, not a silent no-op
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rows", "bogus"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 2 and "unknown --rows" in p.stderr


# ===========================================================================
# Dashboard rendering (pure functions over frames)
# ===========================================================================

def test_dash_state_and_render():
    from repro.obs.dash import DashState, render
    state = DashState()
    state.update({"t": 100.0, "type": "hello",
                  "payload": {"source": "w0", "pid": 1}})
    for i in range(3):
        state.update(_tick_frame(100.0 + i, scenario="steady", seed=0,
                                 policy="edf", tick=i, queue_depth=5,
                                 in_flight=2, dropped=0, window_qos=0.8,
                                 miss_rate=0.1))
    state.update({"t": 103.0, "type": "worker",
                  "payload": {"owner": "w0", "tasks_done": 2,
                              "items_done": 8, "items_per_s": 4.0,
                              "queue_pending_items": 8}})
    state.update({"t": 103.5, "type": "chunk", "payload": {"items": 4}})
    assert state.tick_rate(state.ticks[("steady", 0, "edf")]) == \
        pytest.approx(1.0)
    screen = render(state)
    assert "steady" in screen and "edf" in screen
    assert "w0" in screen and "2s" in screen        # ETA = 8 items / 4/s
    assert "sweep chunks: 1" in screen
    assert "deadline-miss-rate" in screen           # SLO pane, n > 0
    assert "repro.obs dash" in screen and "1 source(s)" in screen


def test_run_dash_once_empty_stream_exits_2(tmp_path):
    from repro.obs.dash import run_dash
    path = tmp_path / "empty.jsonl"
    path.write_text("")
    out = io.StringIO()
    assert run_dash([str(path)], once=True, out=out) == 2
    assert "no tick frames yet" in out.getvalue()


def test_run_dash_reports_stream_errors(tmp_path):
    from repro.obs.dash import run_dash
    path = tmp_path / "bad.jsonl"
    path.write_text('{"seq": 0, "type": "tick", "payload": {}}\n')
    out = io.StringIO()
    assert run_dash([str(path)], once=True, out=out) == 1
    assert "stream error" in out.getvalue()
