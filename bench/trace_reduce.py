"""From a JAX profiler trace to the numbers the per-layer metrics read.

``start(ops)`` turns the profiler on (no Python tracer, no HLO protos)
into a fresh directory under ``$TMPDIR``; ``stop()`` turns it off,
reduces the trace, deletes the directory and returns a
:class:`TraceSummary`. Two files of one trace are read, on one clock:

* the ``.xplane.pb``, through ``jax.profiler.ProfileData``: each TPU
  device plane's program executions (line ``XLA Modules``, one event per
  program run, named ``jit_<function>(<fingerprint>)``) and the host's
  events, among them the harness's ``bench.window`` and ``bench.<unit>``
  spans, which fix the window;
* with ``ops``, the Perfetto JSON the profiler writes beside it: each
  device's op events (thread ``XLA Ops``) with their scope path
  (``tf_op``, e.g. ``jit(run)/while/body/jit(greedy_argmax)/
  greedy_argmax_pallas/pallas_call``), which ``jax.named_scope`` writes.
  Only a cell that attributes time to scopes asks for it: a sweep makes
  hundreds of thousands of op events a second.

Busy time is the union of program executions inside the window; idle is
the rest of the window.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import os
import re
import shutil
import tempfile
from collections import Counter
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_LINE = "python"          # the thread that runs the harness and JAX
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Events:
    """Parallel arrays of one line's events, in ns."""

    start: np.ndarray
    dur: np.ndarray
    name: List[str]
    scope: List[str]           # an op's scope path ("" where none)

    @classmethod
    def make(cls, rows) -> "Events":
        rows = list(rows)
        return cls(np.array([r[0] for r in rows], np.int64),
                   np.array([r[1] for r in rows], np.int64),
                   [r[2] for r in rows],
                   [r[3] if len(r) > 3 else "" for r in rows])

    @property
    def end(self) -> np.ndarray:
        return self.start + self.dur

    def __len__(self) -> int:
        return len(self.name)

    def select(self, mask) -> "Events":
        idx = np.nonzero(np.asarray(mask, bool))[0]
        return Events(self.start[idx], self.dur[idx],
                      [self.name[i] for i in idx],
                      [self.scope[i] for i in idx])


def union_ns(start: np.ndarray, end: np.ndarray, lo: int, hi: int) -> int:
    """Length of the union of ``[start, end)`` intervals within
    ``[lo, hi)``."""
    return (hi - lo) - sum(b - a for a, b in gaps(start, end, lo, hi))


def gaps(start: np.ndarray, end: np.ndarray, lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The intervals of ``[lo, hi)`` that no ``[start, end)`` covers."""
    s = np.clip(start, lo, hi)
    e = np.clip(end, lo, hi)
    order = np.argsort(s, kind="stable")
    out, t = [], lo
    for a, b in zip(s[order].tolist(), e[order].tolist()):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def leaves(ev: Events) -> Events:
    """The ops that hold no other op: a ``while`` or ``conditional`` op
    spans the ops of its body, which the trace lists as well."""
    order = np.argsort(ev.start, kind="stable")
    s, e = ev.start[order], ev.end[order]
    holds = np.zeros(len(ev), bool)
    holds[:-1] = (s[1:] < e[:-1]) & (e[1:] <= e[:-1])
    keep = np.zeros(len(ev), bool)
    keep[order] = ~holds
    return ev.select(keep)


def program(name: str) -> str:
    """``jit_run(1234)`` → ``jit_run``."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Device:
    index: int
    modules: Events            # program executions
    ops: Events                # op events (empty unless asked for)


@dataclasses.dataclass
class TraceSummary:
    devices: List[Device]
    host: Events               # the main thread's host events
    window: Tuple[int, int]    # ns, from the bench.window span

    def in_window(self, ev: Events) -> np.ndarray:
        lo, hi = self.window
        return (ev.start >= lo) & (ev.end <= hi)

    # -- whole-device numbers -------------------------------------------------
    def busy_ns(self, dev: Device) -> int:
        return union_ns(dev.modules.start, dev.modules.end, *self.window)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return float(np.mean([self.busy_ns(d) for d in self.devices])) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # -- attribution ----------------------------------------------------------
    def programs(self, dev: Device, pattern: str) -> Events:
        """Executions in the window of the programs whose name matches
        ``pattern`` (a regular expression, matched at the start)."""
        rx = re.compile(pattern)
        hit = [bool(rx.match(n)) for n in dev.modules.name]
        return dev.modules.select(np.asarray(hit, bool)
                                  & self.in_window(dev.modules))

    def scope_ops(self, dev: Device, scopes) -> Events:
        """Ops in the window under any of the named ``scopes``."""
        scopes = tuple(scopes)
        hit = [any(f"/{s}/" in p for s in scopes) for p in dev.ops.scope]
        return dev.ops.select(np.asarray(hit, bool)
                              & self.in_window(dev.ops))

    def host_span_at(self, t: int) -> str:
        """The innermost host span open at ``t``."""
        h = self.host
        open_ = (h.start <= t) & (h.end > t)
        if not open_.any():
            return "(no host span)"
        idx = np.nonzero(open_)[0]
        return h.name[int(idx[np.argmin(h.dur[idx])])]

    # -- the result line's breakdown --------------------------------------------
    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """Seconds of the window's device time by op scope (by program
        where ops were not read), summed over the devices, and device 0's
        idle seconds by the innermost host span open when each gap began.
        """
        totals: Counter = Counter()
        for dev in self.devices:
            if len(dev.ops):
                ev = leaves(dev.ops.select(self.in_window(dev.ops)))
                keys = [f"{s.rstrip(':')} {n}".strip()
                        for s, n in zip(ev.scope, ev.name)]
            else:
                ev = dev.modules.select(self.in_window(dev.modules))
                keys = [program(n) for n in ev.name]
            for k, d in zip(keys, ev.dur.tolist()):
                totals[k] += d
        idle: Counter = Counter()
        if self.devices:
            m = self.devices[0].modules
            for a, b in gaps(m.start, m.end, *self.window):
                idle[self.host_span_at(a)] += b - a
        return {"device_ops": [[k, v / 1e9] for k, v in totals.most_common(top)],
                "idle_gaps": [[k, v / 1e9] for k, v in idle.most_common(top)]}


def _line_events(line) -> Events:
    return Events.make((int(ev.start_ns), int(ev.duration_ns), ev.name)
                       for ev in line.events)


def read(xplane: os.PathLike, perfetto: Optional[os.PathLike] = None
         ) -> TraceSummary:
    """Reduce one trace: its ``.xplane.pb`` and, for ops, its JSON."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    devices, host = {}, Events.make([])
    window_rows = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            mods = [line for line in plane.lines if line.name == MODULES_LINE]
            devices[int(m.group(1))] = Device(
                int(m.group(1)),
                _line_events(mods[0]) if mods else Events.make([]),
                Events.make([]))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name == HOST_LINE:
                    host = _line_events(line)
                window_rows += [(int(e.start_ns), int(e.duration_ns))
                                for e in line.events if e.name == WINDOW_SPAN]
    if not window_rows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    start, dur = window_rows[-1]
    if perfetto is not None:
        for index, ops in _perfetto_ops(perfetto).items():
            if index in devices:
                devices[index].ops = ops
    return TraceSummary(devices=[devices[i] for i in sorted(devices)],
                        host=host, window=(start, start + dur))


def _perfetto_ops(path: os.PathLike) -> Dict[int, Events]:
    """Each device's ``XLA Ops`` events from the profiler's Perfetto JSON
    (timestamps in µs on the trace's clock)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    planes, lines = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            m = DEVICE_PLANE.match(e["args"]["name"])
            if m:
                planes[e["pid"]] = int(m.group(1))
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            lines[(e["pid"], e["tid"])] = e["args"]["name"]
    rows: Dict[int, list] = {i: [] for i in planes.values()}
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in planes and \
                lines.get((e["pid"], e.get("tid"))) == OPS_LINE:
            rows[planes[e["pid"]]].append(
                (int(round(e["ts"] * 1e3)), int(round(e["dur"] * 1e3)),
                 e["name"], e.get("args", {}).get("tf_op", "")))
    return {i: Events.make(r) for i, r in rows.items()}


def start(ops: bool) -> str:
    """Start the profiler (and the program's obs spans, mirrored into
    the trace) into a new directory under ``$TMPDIR``."""
    import jax

    from repro import obs

    obs.enable(jax_annotations=True)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, create_perfetto_trace=ops,
                             profiler_options=opts)
    return log_dir


def stop(log_dir: str, devices=None) -> TraceSummary:
    """Stop the profiler, reduce its trace, delete the directory."""
    import jax

    from repro import obs

    jax.profiler.stop_trace()
    obs.disable()
    try:
        xplanes = sorted(Path(log_dir).rglob("*.xplane.pb"))
        if not xplanes:
            raise FileNotFoundError(f"the profiler wrote no trace in {log_dir}")
        jsons = sorted(Path(log_dir).rglob("*.json.gz"))
        summary = read(xplanes[-1], jsons[-1] if jsons else None)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    if devices is not None:
        # the cell's chips are the host's first, as the harness takes them
        summary.devices = summary.devices[:len(devices)]
    return summary
