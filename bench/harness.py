"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one cell is found by name in files of its own:

* ``BENCHMARK.json`` — the cells, their configurations and metrics;
* ``bench/configs/<config>.json`` — a deployment (named by the manifest);
* ``bench/traffic/<traffic>.json`` — a traffic mix, which names its driver;
* ``bench/drivers/<driver>.py`` — the entry the window drives, with its
  set-up, its step and its comparison with the plain reference;
* ``bench/metrics/<metric>.py`` — one reader per metric;
* ``bench/limits/<cell>.json`` — the limit of each number compared.

A new configuration, mix, driver or metric is a new file and a manifest
entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import the file at ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names."""

    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, Dict]
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: Path

    def driver(self):
        name = self.traffic["driver"]
        return load_module(self.bench_dir / "drivers" / f"{name}.py",
                           f"bench_driver_{name}")

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           f"bench_metric_{metric}")


def load_cell(manifest: Dict, name: str, root: Path = ROOT,
              bench_dir: Path = BENCH) -> Cell:
    """The cell ``name`` of ``manifest``, its files read from ``root``
    (configurations) and ``bench_dir`` (mixes, limits)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in manifest["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in manifest["per_layer"]
                           if _applies(m, name)],
                bench_dir=bench_dir)


@dataclasses.dataclass
class Run:
    """What one run measured: the record every metric reader reads."""

    cell: Cell
    setup_s: float
    window_s: float
    steps: int                  # whole ticks or passes in the window
    items: int                  # answers the window produced
    facts: Dict[str, Any]       # shapes and counts from the driver
    memory_peak_bytes: Optional[int] = None
    trace: Any = None           # bench.trace_reduce.TraceSummary
    peaks: Optional[Dict] = None


def judge(numbers: Dict[str, float], limits: Dict[str, Dict]):
    """``(correct, checks)``: every number at or under its limit, and a
    number that is missing or not finite is a failure."""
    checks, ok = {}, True
    for name, spec in limits.items():
        value = numbers.get(name)
        limit = float(spec["limit"])
        good = value is not None and math.isfinite(value) and value <= limit
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def read_metrics(run: Run, metrics: List[Dict]) -> Dict[str, Dict]:
    """Each metric's reader; a reader that finds nothing returns None and
    the metric is left out."""
    out = {}
    for m in metrics:
        value = run.cell.reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_memory_peak(devices) -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.counts = {"compiles": 0, "traces": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def find_chips(cell: Cell):
    """The cell's TPU devices, or None when JAX finds no TPU or fewer
    chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s): no result")
        return None
    return devices[:cell.chips]


def peaks_for(cell: Cell, kind: str) -> Dict:
    """The peaks of one device kind; a kind not in the table is an error."""
    table = load_json(cell.bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, peaks: Dict) -> Dict:
    """Set up, measure, check; returns the result line."""
    import jax

    counter = CompileCounter()
    drv = cell.driver().Driver(cell, seed, devices)
    drv.warm()
    log(f"set-up done at {time.perf_counter() - t_start!r} s")

    # a traced run may stop after the mix's "trace" "steps": a trace
    # holds every op the device ran, and some paths run millions a second
    opts = cell.traffic.get("trace", {})
    last_step = opts.get("steps") if trace else None
    trace_dir = None
    if trace:
        from bench import trace_reduce

        trace_dir = trace_reduce.start(ops=bool(opts.get("ops")))
    # the allocator's peak is process-wide: read at the window's start too,
    # it shows whether the window or set-up set the peak reported
    setup_peak = device_memory_peak(devices)
    counter.active = True
    steps = items = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        while True:
            with jax.profiler.TraceAnnotation(f"bench.{drv.unit}"):
                items += drv.step()
            steps += 1
            if time.perf_counter() - t0 >= seconds or steps == last_step:
                break
        window_s = time.perf_counter() - t0
    counter.active = False
    summary = None
    if trace:
        summary = trace_reduce.stop(trace_dir, devices)
    log(f"window {window_s!r} s: {steps} {drv.unit}s, {items} answers; "
        f"in the window {counter.counts['compiles']} compiles, "
        f"{counter.counts['traces']} traces")

    run = Run(cell=cell, setup_s=setup_s, window_s=window_s, steps=steps,
              items=items, facts=drv.facts(),
              memory_peak_bytes=device_memory_peak(devices), trace=summary,
              peaks=peaks)
    log(f"facts {run.facts}")
    metrics = read_metrics(run, cell.per_layer if trace else cell.end_to_end)

    numbers, failed = drv.check()
    correct, checks = judge(numbers, cell.limits)
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": bool(correct), "attempted": items,
            "failed": int(failed), "metrics": metrics, "device": device,
            "window": {"seconds": window_s, "steps": steps,
                       "compiles": counter.counts["compiles"],
                       "traces": counter.counts["traces"],
                       "memory_peak_bytes_at_start": setup_peak}}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s()
        line["breakdown"] = summary.breakdown()
    line["checks"] = checks
    return line
