"""repro.sweeps — spec expansion/hash stability, store durability,
kill-and-resume, sharded-vs-vmap-vs-host parity, aggregation, CLI."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.sweeps import (SweepSpec, SweepStore, auto_chunk_size,
                          envelope_for, materialize, ratio_frame, run_sweep,
                          summarize, variant_key)
from repro.workloads import evaluate_host

SRC = Path(__file__).resolve().parents[1] / "src"


# ===========================================================================
# Spec expansion + deterministic hashing
# ===========================================================================

def test_expand_is_stably_ordered_and_grid_complete():
    spec = SweepSpec(scenarios=("steady", "flash_crowd"), seeds=(3, 1),
                     n_ticks=2, algos=("egp", "sck"))
    items = spec.expand()
    assert len(items) == 2 * 2 * 2 * 2
    # scenario-major, then algo, then seed (in given order), then tick
    assert [i.scenario for i in items[:8]] == ["steady"] * 8
    assert [(i.seed, i.tick) for i in items[:4]] == [(3, 0), (3, 1),
                                                     (1, 0), (1, 1)]
    assert items[0].executor == "accel" and items[4].executor == "host"
    # re-expansion yields identical keys (resume depends on this)
    assert [i.key() for i in items] == [i.key() for i in spec.expand()]


def test_work_item_keys_are_schema_stable():
    # Pinned hash: changing instance/evaluator semantics must come with a
    # SCHEMA_VERSION bump (which changes this value on purpose).
    spec = SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=1)
    key = spec.expand()[0].key()
    assert key == spec.expand()[0].key()
    assert len(key) == 24 and int(key, 16) >= 0
    # v3: per-item serving metrics persisted at sweep time; pareto reads
    # frontiers from the store (see spec.py)
    assert key == "3cc25f098c2b9bfc3e36fb45"
    # a different accelerator iteration cap is a different result
    capped = SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=1,
                       max_iters=8)
    assert capped.expand()[0].key() != key
    # ...but host-path items ignore it (their reference code has no cap)
    h = SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=1,
                  algos=("sck",))
    h8 = SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=1,
                   algos=("sck",), max_iters=8)
    assert h.expand()[0].key() == h8.expand()[0].key()


def test_item_keys_distinguish_every_axis():
    base = SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=1)
    variants = [
        base,
        SweepSpec(scenarios=("diurnal",), seeds=(0,), n_ticks=1),
        SweepSpec(scenarios=("steady",), seeds=(1,), n_ticks=1),
        SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=1,
                  algos=("agp",)),
        SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=1,
                  force_host=("egp",)),
        SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=1,
                  override_grid=({"n_user_slots": 32},)),
    ]
    keys = [s.expand()[0].key() for s in variants]
    assert len(set(keys)) == len(keys)
    # ticks axis: same spec, later tick
    spec2 = SweepSpec(scenarios=("steady",), seeds=(0,), n_ticks=2)
    k0, k1 = [i.key() for i in spec2.expand()]
    assert k0 == keys[0] and k1 != k0  # n_ticks itself is NOT in the key


def test_duplicate_axis_values_are_deduped():
    spec = SweepSpec(scenarios=("steady", "steady"), seeds=(0, 1, 0),
                     n_ticks=1, algos=("egp", "egp"),
                     override_grid=((), ()))
    assert spec.scenarios == ("steady",)
    assert spec.seeds == (0, 1)
    assert spec.algos == ("egp",)
    assert spec.override_grid == ((),)
    assert len(spec.expand()) == 2


def test_unknown_algo_and_override_are_rejected():
    with pytest.raises(ValueError):
        SweepSpec(algos=("newton",))
    with pytest.raises(ValueError):
        materialize("synthetic", (("n_quarks", 3),), [(0, 0)])


def test_envelope_is_static_and_fits_materialized_instances():
    env = envelope_for("steady")
    insts = materialize("steady", (), [(0, 0), (1, 3)])
    for inst in insts:
        assert inst.U <= env[0] and inst.P <= env[1] and inst.E < env[2]
    assert envelope_for("synthetic", (("n_users", 50),)) == (50, 1000, 11)


def test_materialize_matches_scenario_horizon():
    from repro.workloads import horizon
    ref = horizon("mobility_churn", seed=4, n_ticks=3)
    got = materialize("mobility_churn", (), [(4, t) for t in range(3)])
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a.u_edge, b.u_edge)
        np.testing.assert_allclose(a.u_alpha, b.u_alpha)


def test_auto_chunk_size_bounds_memory_and_rounds_to_mesh():
    env = (96, 96, 8)
    assert auto_chunk_size(env, 1, memory_budget_mb=1e-6) == 1  # floor
    cs = auto_chunk_size(env, 4, memory_budget_mb=64)
    assert cs >= 4 and cs % 4 == 0
    assert auto_chunk_size(env, 4, memory_budget_mb=64, n_items=3) == 3
    big = (1000, 1000, 11)
    assert auto_chunk_size(big, 1, memory_budget_mb=512) < \
        auto_chunk_size(env, 1, memory_budget_mb=512)


# ===========================================================================
# Store durability
# ===========================================================================

def test_store_roundtrip_and_crash_tolerance(tmp_path):
    store = SweepStore(tmp_path)
    store.add_chunk(["k1", "k2"], np.array([1.5, 2.5]),
                    np.array([0.1, 0.2]), {"algo": "egp"})
    store.add_chunk(["k3"], np.array([3.5]), np.array([0.3]))
    # fresh handle reads everything back
    again = SweepStore(tmp_path)
    assert "k1" in again and again.value("k2") == 2.5
    assert again.time("k3") == 0.3 and again.meta("k1") == {"algo": "egp"}
    # a torn (half-written) trailing manifest line is ignored
    with open(tmp_path / "manifest.jsonl", "a") as f:
        f.write('{"shard": "zzz.npz", "keys": ["k4"')
    assert "k4" not in SweepStore(tmp_path)
    # a manifest line whose shard file vanished is dropped, rest survives
    (shard, _) = again._index["k3"]
    (tmp_path / "shards" / shard).unlink()
    survivor = SweepStore(tmp_path)
    assert "k3" not in survivor and "k1" in survivor


def test_store_append_after_torn_line_does_not_glue(tmp_path):
    store = SweepStore(tmp_path)
    store.add_chunk(["k1"], np.array([1.0]), np.array([0.1]))
    # simulate a writer killed mid-append: torn final line, no newline
    with open(tmp_path / "manifest.jsonl", "ab") as f:
        f.write(b'{"shard": "zzz.npz", "keys": ["kX"')
    resumed = SweepStore(tmp_path)
    assert "k1" in resumed and "kX" not in resumed
    resumed.add_chunk(["k2"], np.array([2.0]), np.array([0.2]))
    # the new record starts on a fresh line: both chunks visible on reload
    final = SweepStore(tmp_path)
    assert "k1" in final and "k2" in final and final.value("k2") == 2.0


def test_store_concurrent_handles_never_clobber(tmp_path):
    """Two live handles on one store (fleet workers sharing a directory):
    each append re-reads the manifest under the lock, so a stale handle
    keeps the other writer's lines instead of clobbering them."""
    a = SweepStore(tmp_path)
    b = SweepStore(tmp_path)          # opened before a writes: stale view
    a.add_chunk(["k1"], np.array([1.0]), np.array([0.1]))
    assert "k1" not in b              # stale in memory...
    b.add_chunk(["k2"], np.array([2.0]), np.array([0.2]))
    assert "k1" in b and b.value("k1") == 1.0  # ...refreshed under lock
    fresh = SweepStore(tmp_path)
    assert "k1" in fresh and "k2" in fresh
    assert fresh.value("k1") == 1.0 and fresh.value("k2") == 2.0
    assert len((tmp_path / "manifest.jsonl").read_text().splitlines()) == 2


def test_store_metrics_roundtrip_and_chunk_hooks(tmp_path):
    store = SweepStore(tmp_path)
    store.add_chunk(["k1", "k2"], np.array([1.0, 2.0]),
                    np.array([0.1, 0.2]), {"algo": "edf"},
                    metrics={"served": [5.0, 6.0],
                             "latency": [0.25, float("nan")]})
    store.add_chunk(["k3"], np.array([3.0]), np.array([0.3]))
    again = SweepStore(tmp_path)
    assert again.metrics("k1") == {"served": 5.0, "latency": 0.25}
    m2 = again.metrics("k2")
    assert m2["served"] == 6.0 and np.isnan(m2["latency"])
    assert again.metrics("k3") == {}  # chunk without metrics
    # chunk-granular hooks (the fleet merge path)
    recs = again.chunks()
    assert [r["keys"] for r in recs] == [["k1", "k2"], ["k3"]]
    assert recs[0]["metrics"] == ["latency", "served"]
    data = again.chunk_data(recs[0]["shard"])
    np.testing.assert_array_equal(data["values"], [1.0, 2.0])
    np.testing.assert_array_equal(data["metric_served"], [5.0, 6.0])
    with pytest.raises(AssertionError):
        store.add_chunk(["k4"], np.array([1.0]), np.array([0.1]),
                        metrics={"served": [1.0, 2.0]})  # wrong length


def test_spec_json_roundtrip_and_schema_guard():
    spec = SweepSpec(scenarios=("steady", "flash_crowd"), seeds=(0, 3),
                     n_ticks=2, algos=("egp", "sck"),
                     override_grid=({"n_user_slots": 32},),
                     force_host=("egp",), max_iters=64)
    back = SweepSpec.from_json(spec.to_json())
    assert back == spec
    assert back.fingerprint() == spec.fingerprint()
    assert [i.key() for i in back.expand()] == \
        [i.key() for i in spec.expand()]
    # version skew must fail loudly, not silently re-key every item
    doc = spec.to_json()
    doc["schema_version"] -= 1
    with pytest.raises(ValueError, match="schema"):
        SweepSpec.from_json(doc)


def test_store_key_is_stable_across_seed_and_tick_extension():
    a = SweepSpec(scenarios=("steady",), seeds=(0, 1), n_ticks=2)
    b = SweepSpec(scenarios=("steady",), seeds=tuple(range(8)), n_ticks=4)
    c = SweepSpec(scenarios=("flash_crowd",), seeds=(0, 1), n_ticks=2)
    assert a.store_key() == b.store_key()  # same store → resume, not redo
    assert a.store_key() != c.store_key()
    assert a.fingerprint() != b.fingerprint()  # full spec hash still moves


# ===========================================================================
# Engine: host parity, resume, aggregation
# ===========================================================================

SPEC2 = dict(scenarios=("steady", "flash_crowd"), seeds=(0, 1, 2),
             n_ticks=2, algos=("egp",))


def test_engine_matches_host_path_and_aggregates(tmp_path):
    spec = SweepSpec(**SPEC2)
    res = run_sweep(spec, store_dir=tmp_path / "store")
    assert res.complete
    for name in spec.scenarios:
        insts = materialize(name, (), [(s, t) for s in spec.seeds
                                       for t in range(2)])
        host = evaluate_host(insts, algo="egp").reshape(3, 2)
        np.testing.assert_allclose(res.values[(name, "egp")], host,
                                   atol=1e-4)
    # aggregate ratios from engine values match host-side ratios at 1e-4
    summary = summarize(res)
    for name in spec.scenarios:
        cell = summary["cells"][f"{name}/egp"]
        assert cell["sigma"]["n"] == 6
        assert cell["ratio"]["mean"] == pytest.approx(1.0)  # single algo
        assert cell["sigma"]["ci95"] >= 0.0


def test_rerun_is_a_noop_and_bitwise_identical(tmp_path):
    spec = SweepSpec(**SPEC2)
    d = tmp_path / "store"
    first = run_sweep(spec, store_dir=d)
    n_chunks = first.execution["chunks_computed"]
    assert n_chunks >= 2
    second = run_sweep(spec, store_dir=d)
    assert second.execution["chunks_computed"] == 0
    assert second.execution["items_skipped"] == 12
    for k in first.values:
        np.testing.assert_array_equal(first.values[k], second.values[k])


def test_kill_and_resume_skips_completed_chunks(tmp_path):
    spec = SweepSpec(scenarios=("steady",), seeds=(0, 1), n_ticks=3)
    d = tmp_path / "store"
    # "kill" the sweep after 2 of 3 chunks
    partial = run_sweep(spec, store_dir=d, chunk_size=2, max_chunks=2)
    assert partial.execution["chunks_computed"] == 2
    assert not partial.complete
    assert np.isnan(partial.values[("steady", "egp")]).sum() == 2
    before = (d / "manifest.jsonl").read_text().splitlines()
    assert len(before) == 2

    # resume with a DIFFERENT chunk size: item-granular resume still skips
    done = run_sweep(spec, store_dir=d, chunk_size=4)
    assert done.complete
    assert done.execution["items_skipped"] == 4
    assert done.execution["chunks_computed"] == 1
    after = (d / "manifest.jsonl").read_text().splitlines()
    # completed chunks were appended to, never rewritten or recomputed
    assert after[:2] == before
    resumed_keys = set(json.loads(after[2])["keys"])
    already = {k for line in before for k in json.loads(line)["keys"]}
    assert not (resumed_keys & already)
    # the resumed sweep equals a fresh unstored run bitwise
    fresh = run_sweep(spec)
    np.testing.assert_array_equal(done.values[("steady", "egp")],
                                  fresh.values[("steady", "egp")])


def test_kill_and_resume_is_byte_identical_under_bucketing(tmp_path):
    """Bucketed chunk evaluation must not leak batch composition into item
    values: a killed+resumed bucketed sweep, a fresh bucketed sweep, and a
    global-envelope (bucketed=False) sweep all agree bitwise, and the
    resumed store's values reload bitwise."""
    spec = SweepSpec(scenarios=("steady", "flash_crowd"), seeds=(0, 1),
                     n_ticks=3,
                     override_grid=({}, {"n_user_slots": 48}))
    d = tmp_path / "store"
    partial = run_sweep(spec, store_dir=d, chunk_size=4, max_chunks=2,
                        bucketed=True)
    assert not partial.complete
    done = run_sweep(spec, store_dir=d, chunk_size=3, bucketed=True)
    assert done.complete and done.execution["items_skipped"] == 6
    # chunk meta records the bucketed pad mode on every accel chunk
    metas = [json.loads(line).get("meta", {})
             for line in (d / "manifest.jsonl").read_text().splitlines()]
    assert all(m.get("bucketed") for m in metas if m.get("executor") == "accel")

    fresh = run_sweep(spec, bucketed=True)
    flat = run_sweep(spec, bucketed=False)
    for key in done.values:
        np.testing.assert_array_equal(done.values[key], fresh.values[key])
        np.testing.assert_array_equal(done.values[key], flat.values[key])
    # and a pure reload of the store (no compute) is also bitwise equal
    reload_ = run_sweep(spec, store_dir=d, bucketed=True)
    assert reload_.execution["chunks_computed"] == 0
    for key in done.values:
        np.testing.assert_array_equal(done.values[key], reload_.values[key])


def test_host_executor_and_auto_ratio_reference():
    spec = SweepSpec(scenarios=("synthetic",), seeds=(7, 8), n_ticks=1,
                     algos=("egp", "opt", "sck"),
                     override_grid=({"n_users": 30, "n_edges": 4,
                                     "n_services": 12, "max_impls": 3},))
    res = run_sweep(spec)
    vk = variant_key("synthetic", spec.override_grid[0])
    ratios = ratio_frame(res)  # auto → vs exact opt
    assert np.all(ratios[(vk, "opt")] == 1.0)
    # float32 batched egp vs float64 exact opt: ≤ 1 up to f32 tolerance
    assert np.all(ratios[(vk, "egp")] <= 1.0 + 1e-4)
    assert ratios[(vk, "sck")].mean() <= ratios[(vk, "egp")].mean() + 1e-9
    with pytest.raises(ValueError):
        ratio_frame(res, ref="rnd")  # not swept


# ===========================================================================
# Sharded execution (subprocess: forces 4 host platform devices)
# ===========================================================================

_SHARD_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
from repro.sweeps import SweepSpec, run_sweep

spec = SweepSpec(scenarios=("steady", "flash_crowd"), seeds=(0, 1, 2),
                 n_ticks=2, algos=("egp",))
# chunk_size=5 over 6 items/group -> an uneven chunk of 5 (pads to 8 on 4
# devices) and a chunk of 1 (smaller than the device count; pads to 4)
res = run_sweep(spec, chunk_size=5)
assert res.execution["path"] == "shard_map", res.execution
assert res.execution["n_devices"] == 4, res.execution
assert res.complete
print(json.dumps({f"{v}/{a}": vals.tolist()
                  for (v, a), vals in res.values.items()}))
"""


def test_sharded_equals_vmap_equals_host_on_uneven_chunks(tmp_path):
    script = tmp_path / "sharded_run.py"
    script.write_text(_SHARD_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the child must never take an accelerator
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    sharded = {k: np.array(v) for k, v in
               json.loads(proc.stdout.strip().splitlines()[-1]).items()}

    spec = SweepSpec(**SPEC2)
    vmap_res = run_sweep(spec, chunk_size=5)  # this process: 1 device
    assert vmap_res.execution["path"] == "vmap"
    for name in spec.scenarios:
        # bit-for-bit: sharding is pure batch partitioning, no collectives
        np.testing.assert_array_equal(sharded[f"{name}/egp"],
                                      vmap_res.values[(name, "egp")])
        insts = materialize(name, (), [(s, t) for s in spec.seeds
                                       for t in range(2)])
        host = evaluate_host(insts, algo="egp").reshape(3, 2)
        np.testing.assert_allclose(sharded[f"{name}/egp"], host, atol=1e-4)


# ===========================================================================
# Mesh helpers + CLI plumbing
# ===========================================================================

def test_make_host_mesh_raises_clear_error_on_bad_model_degree():
    import jax

    from repro.launch.mesh import make_host_mesh, make_sweep_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match="divisor"):
        make_host_mesh(model=n + 1)
    mesh = make_sweep_mesh(n_items=3)
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == min(3, n)
    assert make_sweep_mesh().shape["data"] == n


def test_cli_seed_parsing_and_override_grid():
    from repro.sweeps.cli import main, parse_seeds
    assert parse_seeds("0:4") == (0, 1, 2, 3)
    assert parse_seeds("2,5, 9") == (2, 5, 9)
    assert parse_seeds("7") == (7,)
    with pytest.raises(Exception):
        parse_seeds("4:4")


def test_cli_end_to_end_smoke(tmp_path, capsys):
    from repro.sweeps.cli import main
    rc = main(["--scenario", "steady", "--seeds", "0:2", "--ticks", "1",
               "--out", str(tmp_path / "store"), "--validate", "-q",
               "--json", str(tmp_path / "summary.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "steady" in out and "egp" in out
    summary = json.loads((tmp_path / "summary.json").read_text())
    assert summary["cells"]["steady/egp"]["sigma"]["n"] == 2
    assert summary["validate_max_abs_diff"] <= 1e-4
    # resume through the CLI is a no-op
    rc = main(["--scenario", "steady", "--seeds", "0:2", "--ticks", "1",
               "--out", str(tmp_path / "store"), "-q"])
    assert rc == 0


def test_cli_validate_fails_on_uncomputed_cells(tmp_path, capsys):
    from repro.sweeps.cli import main
    # --max-chunks 0 computes nothing: validation must fail, not pass
    # vacuously on all-NaN values
    rc = main(["--scenario", "steady", "--seeds", "0:2", "--ticks", "1",
               "--no-store", "--max-chunks", "0", "--validate", "-q"])
    assert rc == 1
    capsys.readouterr()
