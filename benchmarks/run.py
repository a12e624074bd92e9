"""Benchmark aggregator — one entry per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--full] [--json PATH]

Prints ``name,us_per_call,derived`` CSV rows. --full uses the paper's trial
counts (slow); the default is a reduced-but-faithful pass. --json writes
the same rows as structured JSON (the ``derived`` k=v pairs parsed into
typed fields) under a versioned schema (:data:`BENCH_SCHEMA_VERSION`),
plus the repro.obs metric digests (latency/throughput histogram
summaries) collected while the benchmarks ran — so the BENCH_* perf
trajectory can be captured mechanically (seed: ``BENCH_baseline.json``).

The regression gate and trajectory::

    # run only the fast deterministic rows and diff against the committed
    # baseline: quality fields within tolerance both directions, timings
    # within --max-slowdown; exit 3 on any violation (the CI gate)
    PYTHONPATH=src python -m benchmarks.run \\
        --rows serving_horizon,tuning_fit,obs_overhead \\
        --json /tmp/bench.json --compare BENCH_baseline.json \\
        --max-slowdown 25

    # append this run to the schema-versioned perf trajectory
    PYTHONPATH=src python -m benchmarks.run --rows serving_horizon \\
        --trajectory BENCH_trajectory.jsonl

Comparison semantics live in :func:`repro.obs.slo.compare_bench`: fields
with a timing suffix (``_us``/``_ns``/``_ms``/``_per_s``/``_pct``) are
machine-dependent and only bounded by the slowdown factor; everything
else (ratios, QoS, miss rates) is a deterministic simulation output and
must reproduce within ``atol + rtol*|base|``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

#: Version stamp of the --json record layout.
BENCH_SCHEMA_VERSION = 1

#: Version stamp of the --trajectory JSONL record layout.
BENCH_TRAJ_SCHEMA_VERSION = 1

#: Row-group names accepted by --rows, in run order ("kernels" expands to
#: the kernel_* micro rows).
ROW_GROUPS = ("fig3_validation", "fig4_scale", "fig5_realworld",
              "serving_horizon", "tuning_fit", "fleet_scaling",
              "scenario_sweep", "placement_scale", "gateway_soak",
              "kernels", "obs_overhead", "obs_request_trace_overhead",
              "roofline_table")


def _parse_derived(derived: str) -> dict:
    """``"a=1.5;b=2/3;paper=~1.5x"`` → typed fields (float where possible)."""
    out = {}
    for part in derived.split(";"):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            out[part] = True
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


class _Emitter:
    """Prints the classic CSV rows and accumulates structured records."""

    def __init__(self):
        self.rows = []

    def __call__(self, name: str, us_per_call: float, derived: str) -> None:
        # one decimal, bare integers unchanged: keeps sub-10us kernel rows
        # meaningful without reformatting the big figure rows
        us = f"{us_per_call:.1f}".rstrip("0").rstrip(".")
        print(f"{name},{us},{derived}")
        self.rows.append({"name": name, "us_per_call": float(us_per_call),
                          "derived": derived,
                          "fields": _parse_derived(derived)})


def _git_rev() -> "str | None":
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=Path(__file__).resolve().parent, capture_output=True,
            text=True, timeout=5).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as structured JSON")
    ap.add_argument("--rows", default=None,
                    help="comma list of row groups to run (of: "
                         + ",".join(ROW_GROUPS) + "); default: all")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="diff this run against a baseline --json document "
                         "(repro.obs.slo.compare_bench); exit 3 on any "
                         "regression")
    ap.add_argument("--max-slowdown", type=float, default=4.0,
                    help="--compare: timing fields may not exceed this "
                         "factor of baseline (raise on noisy CI machines)")
    ap.add_argument("--rtol", type=float, default=0.12,
                    help="--compare: relative tolerance on quality fields")
    ap.add_argument("--atol", type=float, default=0.02,
                    help="--compare: absolute tolerance on quality fields")
    ap.add_argument("--trajectory", default=None, metavar="PATH",
                    help="append this run's rows to a schema-versioned "
                         "JSONL trajectory file")
    ap.add_argument("--placement-us", default=None, metavar="U1,U2,...",
                    help="placement_scale: comma list of user counts to "
                         "measure, overriding the mini/full grids (e.g. "
                         "1000000 for a measured 10^6 sparse row)")
    args = ap.parse_args()
    trials3 = 10 if args.full else 4
    trials4 = 100 if args.full else 3
    trials5 = 100 if args.full else 50

    selected = None
    if args.rows is not None:
        selected = {s.strip() for s in args.rows.split(",") if s.strip()}
        unknown = selected - set(ROW_GROUPS)
        if unknown:
            ap.error(f"unknown --rows group(s): {', '.join(sorted(unknown))}"
                     f" (valid: {', '.join(ROW_GROUPS)})")

    def want(group: str) -> bool:
        return selected is None or group in selected

    emit = _Emitter()
    print("name,us_per_call,derived")

    # one tracer across every benchmark: the instrumented hot paths feed
    # its histograms (serving latency, sweep throughput) as a side effect
    from repro import obs
    tracer = obs.enable()

    if want("fig3_validation"):
        from benchmarks import fig3_validation
        t0 = time.perf_counter()
        s3 = fig3_validation.run(trials=trials3, verbose=False,
                                 literal_agp=args.full)
        dt = (time.perf_counter() - t0) * 1e6 / trials3
        emit("fig3_validation", dt,
             f"egp_ratio={s3['egp']['mean_ratio']:.3f}"
             f";agp_ratio={s3['agp']['mean_ratio']:.3f}"
             f";sck_ratio={s3['sck']['mean_ratio']:.3f}"
             f";paper=0.904/0.900/0.607")

    if want("fig4_scale"):
        from benchmarks import fig4_scale
        t0 = time.perf_counter()
        s4 = fig4_scale.run(trials=trials4, verbose=False)
        dt = (time.perf_counter() - t0) * 1e6 / trials4
        emit("fig4_scale", dt,
             f"egp_over_sck={s4['egp_over_sck']:.2f}"
             f";paper=~1.5x;egp_ratio={s4['egp'].get('mean_ratio', -1):.3f}")

    if want("fig5_realworld"):
        from benchmarks import fig5_realworld
        t0 = time.perf_counter()
        s5 = fig5_realworld.run(trials=trials5, verbose=False)
        dt = (time.perf_counter() - t0) * 1e6 / trials5
        mobile = s5["placements"]["egp"].get("MobileNet", 0)
        total = sum(s5["placements"]["egp"].values())
        emit("fig5_realworld", dt,
             f"egp_mobilenet={mobile}/{total}"
             f";paper=exclusively_mobilenet"
             f";qos_egp={s5['mean_qos']['egp']:.3f}")

    if want("serving_horizon"):
        from benchmarks import serving_horizon
        t0 = time.perf_counter()
        sv = serving_horizon.run(
            seeds=(0,) if not args.full else (0, 1, 2, 3),
            n_ticks=3 if not args.full else 6, verbose=False)
        dt = (time.perf_counter() - t0) * 1e6 / sv["n_runs"]
        edf = sv["per_cell"][("flash_crowd", "edf")]
        fcfs = sv["per_cell"][("flash_crowd", "fcfs")]
        steady = sv["per_cell"][("steady", "edf")]
        emit("serving_horizon", dt,
             f"flash_qos_edf={edf['mean_realized_qos']:.4f}"
             f";flash_miss_edf={edf['miss_rate']:.3f}"
             f";flash_miss_fcfs={fcfs['miss_rate']:.3f}"
             f";steady_qos_edf={steady['mean_realized_qos']:.4f}"
             f";dropped={edf['dropped']}")

    if want("tuning_fit"):
        from benchmarks import tuning
        t0 = time.perf_counter()
        tn = tuning.run(seeds=(0,) if not args.full else (0, 1),
                        n_ticks=2 if not args.full else 4, verbose=False)
        dt = (time.perf_counter() - t0) * 1e6 / tn["n_items"]
        flash = tn["table"]["flash_crowd"]
        emit("tuning_fit", dt,
             f"flash_sw={flash['switching_cost']:g}"
             f";flash_stick={flash['stickiness']:g}"
             f";flash_qos={flash['mean_qos']:.4f}"
             f";frontier={tn['frontier_sizes']['flash_crowd']}"
             f";fit_us={tn['fit_s'] * 1e6:.0f}")

    if want("fleet_scaling"):
        from benchmarks import fleet_scaling
        t0 = time.perf_counter()
        fl = fleet_scaling.run(
            worker_counts=(1, 2, 4),
            seeds=(0,) if not args.full else (0, 1, 2, 3),
            n_ticks=2 if not args.full else 4, verbose=False)
        dt = (time.perf_counter() - t0) * 1e6 / max(fl["n_items"], 1)
        per_n = fl["workers"]
        emit("fleet_scaling", dt,
             f"items={fl['n_items']}"
             + "".join(f";w{n}_items_per_s={per_n[n]['items_per_s']:.2f}"
                       for n in sorted(per_n))
             + f";single_items_per_s={fl['single_items_per_s']:.2f}")

    if want("scenario_sweep"):
        from benchmarks import scenarios
        sc = scenarios.run(seeds=(0, 1) if not args.full else (0, 1, 2, 3),
                           n_ticks=4 if not args.full else 8, verbose=False)
        # us_per_call is the engine's chunked accelerator evaluation (incl.
        # compile), not the host-side validation loop scenarios.run also
        # does.
        dt = sc["batched_s"] * 1e6 / sc["n_instances"]
        dyn = sc["dynamic"]["flash_crowd"]
        emit("scenario_sweep", dt,
             f"n={sc['n_instances']}"
             f";scenarios={sc['n_scenarios']}"
             f";max_abs_diff={sc['max_abs_diff']:.1e}"
             f";host_us={sc['host_s'] * 1e6 / sc['n_instances']:.0f}"
             f";hyst_minus_greedy={dyn['hysteresis'] - dyn['greedy']:.1f}")

    if want("placement_scale"):
        from benchmarks import placement_scale
        ps_us = (1000, 10_000, 100_000) if args.full else (1000,)
        if args.placement_us:
            ps_us = tuple(int(s) for s in args.placement_us.split(",")
                          if s.strip())
        t0 = time.perf_counter()
        ps = placement_scale.run(us=ps_us, verbose=False)
        dt = (time.perf_counter() - t0) * 1e6 / len(ps_us)
        parts = []
        for lbl, rec in ps["per_u"].items():
            parts.append(f"sparse_{lbl}_ms={rec['sparse_ms']:.2f}")
            if "dense_ms" in rec:
                parts.append(f"dense_{lbl}_ms={rec['dense_ms']:.2f}")
            parts.append(f"mem_ratio_{lbl}={rec['mem_ratio']:.0f}")
            # speedup is a ratio of two timings — machine-dependent, so it
            # only goes into --full rows (the trajectory), never the mini
            # row the CI --compare gate checks as a quality field
            if args.full and "speedup" in rec:
                parts.append(f"speedup_{lbl}={rec['speedup']:.1f}")
        if "u1000k" not in ps["per_u"]:
            # the 10^6 cell's memory story stays in the mini gate even
            # when the cell isn't run: the bytes models are exact given
            # the catalog shape (P, k), which any measured U pins down —
            # the measured 10^6 row itself lives in the trajectory
            # (--placement-us 1000000)
            r0 = next(iter(ps["per_u"].values()))
            u6, e6 = 1_000_000, max(10, 1_000_000 // 1000)
            ratio6 = (placement_scale.dense_bytes(u6, r0["P"], e6)
                      / placement_scale.sparse_bytes(u6, r0["P"], e6,
                                                     r0["k"]))
            parts.append(f"mem_ratio_u1000k={ratio6:.0f}")
        if ps["rel_diff_paper"] is not None:
            parts.append(f"rel_diff_paper={ps['rel_diff_paper']:.2e}")
        bm = ps.get("bucket_mix")
        if bm:
            parts.append(f"bucketed_mix_ms={bm['bucket_ms']:.2f}"
                         f";global_pad_ms={bm['global_ms']:.2f}"
                         f";pad_waste_pct={bm['pad_waste'] * 100:.1f}")
        emit("placement_scale", dt, ";".join(parts))

    if want("gateway_soak"):
        from benchmarks import gateway_soak
        t0 = time.perf_counter()
        gs = gateway_soak.run(full=args.full, verbose=False)
        dt = (time.perf_counter() - t0) * 1e6 / max(gs["ticks"], 1)
        # ticks / bounded / drops / admitted fraction are the soak's
        # operational invariants (quality fields); throughput and the
        # latency quantiles are machine speed (timing suffixes)
        emit("gateway_soak", dt,
             f"ticks={gs['ticks']}"
             f";bounded={int(gs['bounded'])}"
             f";ok={int(gs['ok'])}"
             f";dropped={gs['dropped_ingress']}"
             f";admitted_frac={gs['admitted'] / max(gs['sent'], 1):.3f}"
             f";admitted_per_s={gs['sustained_rps']:.1f}"
             f";p99_admission_ms={gs['p99_admission_ms']:.2f}"
             f";p99_lag_ms={gs['p99_loop_lag_ms']:.2f}")

    if want("kernels"):
        from benchmarks import kernels_micro
        for name, us, derived in kernels_micro.run(verbose=False):
            emit(f"kernel_{name}", us, derived)

    if want("obs_overhead"):
        from benchmarks import serving_horizon
        ov = serving_horizon.obs_overhead()
        emit("obs_overhead", ov["noop_span_ns"] / 1e3,
             f"disabled_pct={ov['disabled_pct']:.4f}"
             f";enabled_pct={ov['enabled_pct']:.2f}"
             f";events={ov['n_events']}"
             f";noop_span_ns={ov['noop_span_ns']:.0f}")

    if want("obs_request_trace_overhead"):
        from benchmarks import serving_horizon
        ov = serving_horizon.reqtrace_overhead()
        # `kept` is deterministic for the fixed (config, seed, sampling)
        # — the quality field; the rest is machine speed
        emit("obs_request_trace_overhead", ov["disabled_noop_ns"] / 1e3,
             f"kept={ov['kept']}"
             f";disabled_noop_ns={ov['disabled_noop_ns']:.0f}"
             f";enabled_sampled_pct={ov['enabled_sampled_pct']:.2f}")

    if want("roofline_table"):
        from benchmarks import roofline
        rows = roofline.build(verbose=False)
        # analytic placement rows carry no roofline_fraction — keep them
        # out of the HLO-derived aggregate
        ok_rows = [r for r in rows
                   if "skip" not in r and "roofline_fraction" in r]
        if ok_rows:
            worst = min(ok_rows, key=lambda r: r["roofline_fraction"])
            best = max(ok_rows, key=lambda r: r["roofline_fraction"])
            import numpy as np
            med = float(np.median([r["roofline_fraction"]
                                   for r in ok_rows]))
            emit("roofline_table", 0,
                 f"cells={len(ok_rows)};median_fraction={med:.3f}"
                 f";worst={worst['arch']}/{worst['shape']}"
                 f"={worst['roofline_fraction']:.3f}"
                 f";best={best['arch']}/{best['shape']}"
                 f"={best['roofline_fraction']:.3f}")
        else:
            emit("roofline_table", 0,
                 "no_dryrun_artifacts=1;hint=run repro.launch.dryrun")

    doc = {
        "bench_schema": BENCH_SCHEMA_VERSION,
        "full": bool(args.full),
        "rows": emit.rows,
        "obs": {
            "histograms": tracer.metrics.histograms(),
            "counters": dict(tracer.counters),
            "n_spans": tracer.n_spans,
        },
    }
    if args.json:
        path = Path(args.json)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(doc, indent=1))

    if args.trajectory:
        path = Path(args.trajectory)
        path.parent.mkdir(parents=True, exist_ok=True)
        rec = {
            "bench_traj_schema": BENCH_TRAJ_SCHEMA_VERSION,
            "t": round(time.time(), 3),
            "git_rev": _git_rev(),
            "full": bool(args.full),
            "rows": [{"name": r["name"], "us_per_call": r["us_per_call"],
                      "fields": r["fields"]} for r in emit.rows],
        }
        with path.open("a") as fh:
            fh.write(json.dumps(rec, separators=(",", ":"),
                                sort_keys=True) + "\n")
        print(f"[bench] appended {len(emit.rows)} row(s) to {path}",
              file=sys.stderr)

    rc = 0
    if args.compare:
        from repro.obs.slo import compare_bench
        base = json.loads(Path(args.compare).read_text())
        have = int(base.get("bench_schema", -1))
        if have != BENCH_SCHEMA_VERSION:
            print(f"[bench] baseline {args.compare} has bench_schema "
                  f"v{have}, this code writes v{BENCH_SCHEMA_VERSION}",
                  file=sys.stderr)
            rc = 3
        else:
            cmp_rows = None
            if selected is not None:
                cmp_rows = set()
                for group in selected:
                    if group == "kernels":
                        cmp_rows |= {r["name"] for r in emit.rows
                                     if r["name"].startswith("kernel_")}
                    else:
                        cmp_rows.add(group)
            res = compare_bench(doc, base, max_slowdown=args.max_slowdown,
                                rtol=args.rtol, atol=args.atol,
                                rows=cmp_rows)
            if res["violations"]:
                print(f"[bench] REGRESSION vs {args.compare} "
                      f"({len(res['violations'])} violation(s) over "
                      f"{len(res['rows_checked'])} row(s)):",
                      file=sys.stderr)
                for v in res["violations"]:
                    print(f"  {v}", file=sys.stderr)
                rc = 3
            else:
                print(f"[bench] no regression vs {args.compare}: "
                      f"{len(res['rows_checked'])} row(s), "
                      f"{res['fields_checked']} field(s) checked",
                      file=sys.stderr)
    obs.disable()
    return rc


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
