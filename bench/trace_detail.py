"""What a traced run's result line cannot show: device idle by what the
host was doing, and the sparse greedy's device time by phase.

    python3 bench/trace_detail.py --workload <cell> --seed <n> --seconds <s> \
        [--keep <dir>]
    python3 bench/trace_detail.py --xplane <file>

The first makes one traced run of the cell in this process, as
``bench/run.py --trace 1`` does, prints its result line, then one more
JSON line, ``{"trace_detail": {...}}``; ``--keep`` copies the run's
``.xplane.pb`` into a directory. The second reads a kept ``.xplane.pb``
(all but ``greedy``, which needs the run's compiled program):

* ``host_lines``: each line of the host plane, its number of events and
  whether it holds the ``bench.window`` span;
* ``idle_s``: each device's idle seconds in the window;
* ``idle_by_span``: those seconds, mean over the devices, by the innermost
  host span open when each idle gap began (the rule of the result line's
  ``breakdown``, which files a whole gap under what the host did first);
* ``idle_during_span``: the same seconds by the innermost host span open
  at each moment of each gap, and ``idle_during_phase`` by the innermost
  of the harness's and the program's own spans (``bench.*``,
  ``placement.*``, ``sweep.*``) only, not what JAX records inside them
  (``PjitFunction(...)``, ``DevicePut``);
* ``input_idle_pct``: % of the window in which the chips idle (mean over
  them) while that span is ``sweep.materialize`` or ``sweep.pad``;
* where the cell runs the sparse greedy, ``greedy``: the device seconds
  of its program's leaf ops by phase (``bench/greedy_phases.py``), their
  share of the leaf ops and of the program's executions, and the largest
  ops outside the phases.

Why the host events are read anew: ``trace_reduce.read`` takes them from
the host line named ``python``. The profiler names that line after the
process's main thread, ``python3`` under the benchmark's command, and the
result line's breakdown then files every idle gap under ``(no host
span)``. Here they come from the line or lines that hold ``bench.window``.
The reduction deletes the trace once read, so the lines are kept as it
reads them.
"""
import dataclasses
import json
import sys
from collections import Counter
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import greedy_phases  # noqa: E402
from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402  (its clock starts here)
from bench import trace_reduce as tr  # noqa: E402

PHASE_SPANS = ("bench.", "placement.", "sweep.")
INPUT_SPANS = ("sweep.materialize", "sweep.pad")
NO_SPAN = "(no host span)"


def host_lines(pd) -> List[Dict]:
    """Each line of the host plane of a ``ProfileData``: its name, its
    number of events and whether it holds ``bench.window``."""
    out = []
    for plane in pd.planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                names = [e.name for e in line.events]
                out.append({"line": line.name, "events": len(names),
                            "window": tr.WINDOW_SPAN in names})
    return out


def window_lines(pd) -> tr.Events:
    """The events of every host line that holds ``bench.window``: the
    thread that runs the harness, and with it the program's spans."""
    rows = []
    for plane in pd.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            ev = [(int(e.start_ns), int(e.duration_ns), e.name)
                  for e in line.events]
            if any(r[2] == tr.WINDOW_SPAN for r in ev):
                rows += ev
    return tr.Events.make(sorted(rows))


def with_host(summary: tr.TraceSummary, pd) -> tr.TraceSummary:
    """``summary`` with its host events from :func:`window_lines`."""
    return dataclasses.replace(summary, host=window_lines(pd))


def idle_s(summary: tr.TraceSummary):
    """Each device's idle seconds in the window."""
    return [(summary.window[1] - summary.window[0]
             - summary.busy_ns(d)) / 1e9 for d in summary.devices]


def idle_by_span(summary: tr.TraceSummary) -> Dict[str, float]:
    """Idle seconds, mean over the devices, by the innermost host span
    open when each gap began."""
    idle: Counter = Counter()
    for dev in summary.devices:
        m = dev.modules
        for a, b in tr.gaps(m.start, m.end, *summary.window):
            idle[summary.host_span_at(a)] += (b - a) / 1e9
    n = max(len(summary.devices), 1)
    return {k: v / n for k, v in idle.most_common()}


def phases_only(summary: tr.TraceSummary) -> tr.TraceSummary:
    """``summary`` with only the harness's and the program's own host
    spans (:data:`PHASE_SPANS`), not what JAX records inside them."""
    h = summary.host
    keep = [n.startswith(PHASE_SPANS) for n in h.name]
    return dataclasses.replace(summary, host=h.select(keep))


def timeline(host: tr.Events):
    """The innermost host span through time, as ``(bounds, names)``:
    ``names[i]`` holds ``[bounds[i], bounds[i + 1])``. The spans of one
    thread nest; one that outlasts its parent is cut at the parent's end."""
    bounds: List[int] = []
    names: List[str] = []

    def mark(t: int, name: str) -> None:
        if bounds and bounds[-1] == t:
            names[-1] = name
        else:
            bounds.append(t)
            names.append(name)

    def pop() -> None:
        end = stack.pop()[0]
        mark(end, stack[-1][1] if stack else NO_SPAN)

    stack: List = []
    for i in sorted(range(len(host)),
                    key=lambda i: (host.start[i], -host.dur[i])):
        start, end = int(host.start[i]), int(host.end[i])
        while stack and stack[-1][0] <= start:
            pop()
        if stack:
            end = min(end, stack[-1][0])
        stack.append((end, host.name[i]))
        mark(start, host.name[i])
    while stack:
        pop()
    return np.asarray(bounds, np.int64), names


def idle_during(summary: tr.TraceSummary) -> Dict[str, float]:
    """Idle seconds, mean over the devices, by the innermost host span
    open at each moment of each gap: a gap that outlasts a span is split
    between it and what the host did next."""
    bounds, names = timeline(summary.host)
    idle: Counter = Counter()
    for dev in summary.devices:
        m = dev.modules
        for a, b in tr.gaps(m.start, m.end, *summary.window):
            i = int(np.searchsorted(bounds, a, side="right")) - 1
            t = a
            while t < b:
                nxt = int(bounds[i + 1]) if i + 1 < len(bounds) else b
                idle[names[i] if i >= 0 else NO_SPAN] += \
                    (min(b, nxt) - t) / 1e9
                t, i = min(b, nxt), i + 1
    n = max(len(summary.devices), 1)
    return {k: v / n for k, v in idle.most_common()}


def input_idle_pct(summary: tr.TraceSummary) -> float:
    """% of the window in which the chips idle (mean over them) while the
    innermost of the program's spans is one that draws or pads the
    sweep's inputs (:data:`INPUT_SPANS`)."""
    by = idle_during(phases_only(summary))
    return 100.0 * sum(by.get(s, 0.0) for s in INPUT_SPANS) \
        / summary.window_s()


def greedy_detail(run, top: int = 8) -> Optional[Dict]:
    """Device seconds of the greedy program's leaf ops by phase, with
    their share of the leaf ops and of the program's executions; None
    where :func:`greedy_phases.greedy_ops` finds no phases."""
    ops = greedy_phases.greedy_ops(run)
    if ops is None or not len(ops):
        return None
    dev = run.trace.devices[0]
    program_ns = int(run.trace.programs(dev, greedy_phases.PROGRAM)
                     .dur.sum())
    hit = np.zeros(len(ops), bool)
    phases = {}
    for p in greedy_phases.PHASES:
        mine = np.array([f"/{p}/" in s for s in ops.scope], bool)
        phases[p] = int(ops.dur[mine].sum()) / 1e9
        hit |= mine
    outside: Counter = Counter()
    for n, s, d in zip(ops.name, ops.scope, ops.dur.tolist()):
        if not any(f"/{p}/" in s for p in greedy_phases.PHASES):
            outside[f"{s.rstrip(':')} {n}".strip()] += d
    leaf_ns = int(ops.dur.sum())
    return {"program_s": program_ns / 1e9, "leaf_s": leaf_ns / 1e9,
            "phases_s": phases,
            "cover_of_leaves": int(ops.dur[hit].sum()) / leaf_ns,
            "cover_of_program": int(ops.dur[hit].sum()) / program_ns,
            "outside_s": [[k, v / 1e9] for k, v in outside.most_common(top)]}


def detail(summary: tr.TraceSummary, pd, run=None) -> Dict:
    """Everything above for one trace; ``greedy`` where ``run`` is given
    and ran the sparse greedy."""
    s = with_host(summary, pd)
    out = {"host_lines": host_lines(pd), "idle_s": idle_s(s),
           "idle_by_span": idle_by_span(s),
           "idle_during_span": idle_during(s),
           "idle_during_phase": idle_during(phases_only(s)),
           "input_idle_pct": input_idle_pct(s)}
    greedy = greedy_detail(run) if run is not None else None
    if greedy is not None:
        out["greedy"] = greedy
    return out


def main(argv=None) -> int:
    import argparse
    import shutil

    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed")
    ap.add_argument("--seconds")
    ap.add_argument("--keep", metavar="DIR",
                    help="copy the run's .xplane.pb into DIR")
    ap.add_argument("--xplane", metavar="FILE",
                    help="no run: the idle of a kept .xplane.pb")
    args = ap.parse_args(argv)
    if args.xplane:
        print(json.dumps({"trace_detail": detail(
            tr.read(args.xplane), ProfileData.from_file(args.xplane))}))
        return 0
    if not (args.workload and args.seed and args.seconds):
        ap.error("a run needs --workload, --seed and --seconds")

    kept = {}
    read, read_metrics = tr.read, harness.read_metrics

    def keep_read(xplane, perfetto=None):
        kept["pd"] = ProfileData.from_file(str(xplane))
        if args.keep:
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, Path(args.keep) / f"{args.workload}.xplane.pb")
        return read(xplane, perfetto)

    def keep_run(run, metrics):
        kept["run"] = run
        return read_metrics(run, metrics)

    tr.read, harness.read_metrics = keep_read, keep_run
    try:
        rc = bench_run.main(["--workload", args.workload, "--seed", args.seed,
                             "--seconds", args.seconds, "--trace", "1"])
    finally:
        tr.read, harness.read_metrics = read, read_metrics
    if rc == 0:
        run = kept["run"]
        print(json.dumps({"trace_detail": detail(run.trace, kept["pd"],
                                                 run)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
