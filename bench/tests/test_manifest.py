"""``BENCHMARK.json`` against the files it names, and the harness's
promise that a mix (or configuration, driver, metric) is added by files
and manifest entries alone."""
import copy
import json
import re
import shutil
import subprocess
import sys

import numpy as np

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files(manifest):
    root = harness.ROOT
    assert manifest["paths"] == ["bench"]
    for c in manifest["configs"]:
        assert NAME.match(c["name"])
        doc = harness.load_json(root / c["file"])
        assert doc["reduced"] == c["reduced"]
        assert "deployment" in doc and "source" in doc and "assumed" in doc
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"])
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = harness.load_cell(manifest, w["name"])
        assert (harness.BENCH / "drivers"
                / f"{cell.traffic['driver']}.py").is_file()
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names, (w["name"], m["name"])


def test_a_mix_is_added_by_files_alone(manifest, tmp_path, metro_cell):
    """A throwaway mix in a temporary copy of ``bench/``: one new traffic
    file, one new limits file, one new manifest entry."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    mix = dict(metro_cell.traffic, name="metro-zipf-tiny",
               service_popularity={"kind": "zipf", "s": 1.1})
    (bench / "traffic" / "metro-zipf-tiny.json").write_text(json.dumps(mix))
    shutil.copy(bench / "limits" / "metro-tick.json",
                bench / "limits" / "metro-zipf.json")
    doc = copy.deepcopy(manifest)
    doc["workloads"].append({"name": "metro-zipf", "config": "vib-metro",
                             "traffic": "metro-zipf-tiny", "chips": 1,
                             "why": "throwaway"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "metro-tick" in m.get("workloads", []):
            m["workloads"].append("metro-zipf")
    cell = harness.load_cell(doc, "metro-zipf", bench_dir=bench)
    cell.config["deployment"]["edges"]["count"] = 30
    cell.config["deployment"]["users"]["per_tick"] = 3000
    assert cell.traffic["service_popularity"]["kind"] == "zipf"
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in metro_cell.per_layer}

    import jax

    drv = cell.driver().Driver(cell, 5, jax.devices()[:1])
    drv.warm()
    drv.step()
    numbers, failed = drv.check()
    assert harness.judge(numbers, cell.limits)[0], numbers
    counts = np.bincount(drv.populations[0].service, minlength=100)
    assert counts[0] > 5 * counts[50]


def test_command_refuses_the_cpu(tmp_path):
    """Without a TPU the command prints no result and exits nonzero."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path),
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "metro-tick",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "TPU" in out.stderr


def test_command_needs_the_program(tmp_path):
    """In a directory with only BENCHMARK.json and bench/, no result."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "metro-tick",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "{" not in out.stdout
