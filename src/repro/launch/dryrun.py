import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first — jax locks the device count at first
init, and the production meshes need 512 host placeholder devices. Run:

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi_34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod/--single-pod]

Artifacts (memory analysis, cost analysis, per-collective byte totals) are
written to experiments/dryrun/<arch>__<shape>__<mesh>.json, consumed by
benchmarks/roofline.py and EXPERIMENTS.md §Dry-run/§Roofline.
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every tensor literal in an HLO type string
    (handles tuples like ``(f32[8,128], bf16[4])``)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Parse per-device optimized HLO; sum operand bytes per collective op.

    Operand shapes are recovered from each instruction's own result type
    table built in a first pass (covers named operands); fused constants and
    literals contribute 0.
    """
    result_type = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT )?%?([\w.\-]+) = (\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*) ", line)
        if m:
            result_type[m.group(1)] = m.group(2)

    stats = {c: {"count": 0, "operand_bytes": 0, "result_bytes": 0}
             for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT )?%?([\w.\-]+) = (\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*) ([a-z\-]+)\((.*)", line)
        if not m:
            continue
        name, rtype, op, rest = m.groups()
        kind = next((c for c in _COLLECTIVES if op == c or op == c + "-start"
                     or op == c + "-done"), None)
        if kind is None or op.endswith("-done"):
            continue
        stats[kind]["count"] += 1
        stats[kind]["result_bytes"] += _shape_bytes(rtype)
        # operand names up to the closing paren of the call
        args = rest.split(")")[0]
        ob = 0
        for tok in args.split(","):
            tok = tok.strip().lstrip("%")
            tok = tok.split(" ")[0]
            if tok in result_type:
                ob += _shape_bytes(result_type[tok])
        stats[kind]["operand_bytes"] += ob
    stats["total_operand_bytes"] = sum(
        v["operand_bytes"] for k, v in stats.items() if isinstance(v, dict))
    stats["total_result_bytes"] = sum(
        v["result_bytes"] for k, v in stats.items() if isinstance(v, dict))
    return stats


def memory_summary(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if ma is None:
        return {"error": "memory_analysis() returned None"}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes", "host_argument_size_in_bytes",
                 "host_output_size_in_bytes", "host_temp_size_in_bytes",
                 "serialized_size_in_bytes"):
        try:
            v = getattr(ma, attr)
            if isinstance(v, int):
                out[attr] = v
        except Exception:
            pass
    if "argument_size_in_bytes" in out:
        out["per_device_hbm_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, save: bool = True,
             keep_hlo: bool = False) -> dict:
    import jax
    from repro.launch.mesh import make_production_mesh, mesh_devices
    from repro.launch.specs import build_cell
    from repro.launch.shapes import cell_plan

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ok, why = cell_plan(arch, shape)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "status": "skip", "skip_reason": why}
    if not ok:
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh:
        jitted, args, meta = build_cell(arch, shape, mesh, multi_pod)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        cost = dict(compiled.cost_analysis() or {})
        mem = memory_summary(compiled)
        hlo = compiled.as_text()
        colls = collective_stats(hlo)
        from repro.analysis.hlo_cost import analyze_hlo
        corrected = analyze_hlo(hlo)

    cfg = meta["config"]
    rec.update({
        "status": "ok",
        "kind": meta["kind"],
        "devices": mesh_devices(mesh),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory": mem,
        "collectives": colls,          # raw text scan (no trip scaling)
        "corrected": corrected,        # trip-count-aware per-device model
        "n_params": cfg.n_params,
        "n_active_params": cfg.n_active_params,
        "hlo_lines": hlo.count("\n"),
    })
    if keep_hlo:
        rec["hlo_path"] = str(ARTIFACT_DIR / f"{arch}__{shape}__{mesh_name}.hlo")
        ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
        Path(rec["hlo_path"]).write_text(hlo)
    if save:
        ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
        path = ARTIFACT_DIR / f"{arch}__{shape}__{mesh_name}.json"
        path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true", default=None,
                    dest="multi_pod")
    ap.add_argument("--single-pod", action="store_false", dest="multi_pod")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from repro.launch.shapes import all_cells

    if args.all:
        cells = [(a, s) for a, s, ok, _ in all_cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = [False, True] if args.multi_pod is None else [args.multi_pod]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            out = ARTIFACT_DIR / f"{arch}__{shape}__{mesh_name}.json"
            if args.skip_existing and out.exists():
                prev = json.loads(out.read_text())
                if prev.get("status") in ("ok", "skip"):
                    print(f"[cached] {arch} {shape} {mesh_name}: {prev['status']}")
                    continue
            try:
                rec = run_cell(arch, shape, mp, keep_hlo=args.keep_hlo)
                if rec["status"] == "skip":
                    print(f"[skip]   {arch} {shape} {mesh_name}: {rec['skip_reason']}")
                else:
                    mem = rec["memory"].get("per_device_hbm_bytes")
                    memg = f"{mem/2**30:.2f}GiB" if mem else "?"
                    fl = rec["corrected"]["flops"]
                    cb = rec["corrected"]["collectives"]["total_operand_bytes"]
                    print(f"[ok]     {arch} {shape} {mesh_name}: "
                          f"mem/dev={memg} flops/dev={fl:.3e} "
                          f"coll/dev={cb/2**30:.2f}GiB "
                          f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)")
            except Exception as e:
                failures += 1
                print(f"[FAIL]   {arch} {shape} {mesh_name}: {e}")
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "status": "fail", "error": str(e)}
                ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
                out.write_text(json.dumps(rec, indent=1))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
