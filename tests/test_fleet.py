"""repro.fleet — lease-queue mechanics (atomic claims, heartbeat, expiry
requeue), worker drain, crash-safe merge with bit-for-bit duplicate
verification, 1-vs-4-worker subprocess parity with a SIGKILLed worker,
and the ``python -m repro.fleet`` / ``--fleet N`` CLIs."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.fleet import (FleetMergeConflict, LeaseQueue, Task, merge, plan,
                         reap, run_worker, status, task_spec,
                         worker_store_dir)
from repro.sweeps import SweepSpec, SweepStore, run_sweep

SRC = Path(__file__).resolve().parents[1] / "src"

#: Shrunk scenario (see tests/test_horizon.py) — keeps horizons fast.
SMALL = {"n_user_slots": 32, "n_services": 8, "max_impls": 3, "n_edges": 4}


def _grid(knobs=((0.0, 0.0),)):
    return tuple(
        tuple(sorted({**SMALL, "switching_cost": sc,
                      "stickiness": st}.items()))
        for sc, st in knobs)


def _spec(scenarios=("steady",), seeds=(0, 1), algos=("edf",),
          n_ticks=2, knobs=((0.0, 0.0),)):
    return SweepSpec(kind="serving", scenarios=scenarios, seeds=seeds,
                     n_ticks=n_ticks, algos=algos,
                     override_grid=_grid(knobs))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # workers must never take an accelerator
    return env


def _worker_cmd(root, owner, ttl=60.0):
    return [sys.executable, "-m", "repro.fleet", "worker",
            "--root", str(root), "--owner", owner, "--ttl", str(ttl)]


# ===========================================================================
# Queue mechanics
# ===========================================================================

def _task(name="000000_abcd1234", seeds=(0,)):
    return Task(name=name, scenario="steady", overrides=(("a", 1),),
                algo="edf", seeds=tuple(seeds), n_ticks=2,
                keys=(f"k{name}",))


def test_queue_put_claim_complete_roundtrip(tmp_path):
    q = LeaseQueue(tmp_path / "q", owner="w0", ttl=60.0)
    assert q.put(_task()) and not q.put(_task())  # idempotent
    assert q.pending() == ["000000_abcd1234"]
    lease = q.claim()
    assert lease is not None and lease.owner == "w0"
    assert q.pending() == [] and q.leased() == ["000000_abcd1234"]
    # the lease file carries the task doc + owner/expiry block
    doc = json.loads(lease.path.read_text())
    assert doc["lease"]["owner"] == "w0"
    assert Task.from_json(doc) == lease.task
    # no second claimant while leased
    q2 = LeaseQueue(tmp_path / "q", owner="w1", ttl=60.0)
    assert q2.claim() is None
    assert lease.renew()
    assert lease.complete()
    assert q.done() == ["000000_abcd1234"] and q.leased() == []
    st = q.status()
    assert (st["pending"], st["leased"], st["done"]) == (0, 0, 1)


def test_queue_release_returns_task(tmp_path):
    q = LeaseQueue(tmp_path / "q", owner="w0", ttl=60.0)
    q.put(_task())
    lease = q.claim()
    assert lease.release()
    assert q.pending() == ["000000_abcd1234"] and q.leased() == []
    # the requeued doc is clean (no stale lease block)
    doc = json.loads((q.task_dir / "000000_abcd1234.json").read_text())
    assert "lease" not in doc


def test_lease_expiry_reap_and_reclaim(tmp_path):
    q = LeaseQueue(tmp_path / "q", owner="dead-worker", ttl=0.15)
    q.put(_task())
    lease = q.claim()
    assert lease is not None
    # worker "dies": no heartbeat; unexpired lease is not reaped
    assert q.reap(now=lease.expires_at - 0.05) == []
    assert q.status(now=lease.expires_at + 0.05)["expired"] == 1
    assert q.reap(now=lease.expires_at + 0.05) == ["000000_abcd1234"]
    # the task is claimable again by a live worker
    q2 = LeaseQueue(tmp_path / "q", owner="w1", ttl=60.0)
    lease2 = q2.claim()
    assert lease2 is not None and lease2.owner == "w1"
    # the dead worker's stale handle cannot renew, complete, or release
    # the task out from under its new owner
    assert not lease.renew() and lease.lost
    assert not lease.complete() and not lease.release()
    assert lease2.path.exists()
    doc = json.loads(lease2.path.read_text())
    assert doc["lease"]["owner"] == "w1"
    assert lease2.complete()


def test_unreadable_task_is_quarantined_not_parked(tmp_path):
    """An externally corrupted task file must not become an unreapable
    forever-lease: claim quarantines it visibly and moves on."""
    q = LeaseQueue(tmp_path / "q", owner="w0", ttl=60.0)
    # sorts before the healthy task, so claim() visits it first
    (q.task_dir / "000000_aaaaaaaa.json").write_text("{corrupt")
    q.put(_task())
    lease = q.claim()
    assert lease is not None and lease.task.name == "000000_abcd1234"
    st = q.status()
    assert st["leased"] == 1 and st["poisoned"] == \
        ["000000_aaaaaaaa.json.poison"]
    assert q.reap() == []  # the quarantined file is not a lease


def test_heartbeat_keeps_lease_alive(tmp_path):
    q = LeaseQueue(tmp_path / "q", owner="w0", ttl=0.5)
    q.put(_task())
    lease = q.claim()
    for _ in range(3):
        time.sleep(0.1)
        assert lease.renew()
    # a renewed lease is never expired at its original deadline
    assert q.reap() == []
    assert lease.complete()


# ===========================================================================
# Plan / worker / merge — in-process
# ===========================================================================

def test_plan_worker_merge_single_worker_byte_identical(tmp_path):
    spec = _spec(seeds=(0, 1, 2))
    ref = run_sweep(spec, store_dir=tmp_path / "ref")

    root = tmp_path / "fleet"
    pl = plan(spec, root, target_store=tmp_path / "merged")
    assert pl["n_tasks"] == 3 and pl["n_items"] == 6
    summary = run_worker(root, owner="w0")
    assert summary["stop"] == "drained" and summary["n_tasks"] == 3
    mg = merge(root, tmp_path / "merged")
    assert mg["merged_items"] == 6 and mg["missing_items"] == 0

    got = run_sweep(spec, store_dir=tmp_path / "merged")
    assert got.execution["chunks_computed"] == 0  # merge made it complete
    for k in ref.values:
        assert ref.values[k].tobytes() == got.values[k].tobytes()
    # per-item metrics merged intact
    merged = SweepStore(tmp_path / "merged")
    refs = SweepStore(tmp_path / "ref")
    for key in refs.keys():
        assert merged.metrics(key) == refs.metrics(key)
        assert merged.meta(key)["fleet_worker"] == "w0"


def _record_popen(monkeypatch):
    """Stand in for ``subprocess.Popen`` in the worker module; returns the
    list of environments the workers would have been started with."""
    from repro.fleet import worker

    envs = []
    monkeypatch.setattr(worker.subprocess, "Popen",
                        lambda cmd, env, **kw: envs.append(env))
    return envs


def test_host_only_fleet_workers_never_take_an_accelerator(tmp_path,
                                                           monkeypatch):
    from repro.fleet.worker import spawn_local_workers

    plan(_spec(), tmp_path / "fleet", target_store=tmp_path / "store")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    envs = _record_popen(monkeypatch)
    spawn_local_workers(tmp_path / "fleet", 3)
    assert [e["JAX_PLATFORMS"] for e in envs] == ["cpu"] * 3


def test_device_fleet_refuses_more_workers_than_one_process(tmp_path,
                                                           monkeypatch):
    from repro.fleet import worker

    spec = SweepSpec(scenarios=("steady",), seeds=(0, 1), n_ticks=1,
                     algos=("egp",))
    plan(spec, tmp_path / "fleet", target_store=tmp_path / "store")
    monkeypatch.setattr(worker, "worker_devices", lambda env: ("tpu", 1))
    envs = _record_popen(monkeypatch)
    with pytest.raises(RuntimeError, match="one process holds a chip"):
        worker.spawn_local_workers(tmp_path / "fleet", 2)
    assert envs == []
    worker.spawn_local_workers(tmp_path / "fleet", 1)  # one worker may
    assert len(envs) == 1


def test_plan_skips_completed_seeds_and_rejects_foreign_spec(tmp_path):
    spec = _spec(seeds=(0, 1))
    run_sweep(spec, store_dir=tmp_path / "store")  # everything done
    pl = plan(spec, tmp_path / "fleet", target_store=tmp_path / "store")
    assert pl["n_tasks"] == 0 and pl["skipped_items"] == 4
    # a different spec cannot reuse the fleet root
    with pytest.raises(ValueError, match="one fleet root"):
        plan(_spec(seeds=(0, 1, 2)), tmp_path / "fleet")


def test_replan_after_partial_completion_enqueues_nothing_new(tmp_path):
    """Task names are pure content hashes: re-planning after some tasks
    completed (their seeds gone from the pending set) regenerates the
    SAME names for the survivors — nothing is duplicated, nothing is
    re-executed."""
    spec = _spec(seeds=(0, 1, 2, 3))
    root, store = tmp_path / "fleet", tmp_path / "store"
    plan(spec, root, target_store=store)
    run_worker(root, owner="w0", max_tasks=2)   # partial drain
    merge(root, store)
    q = LeaseQueue(root / "queue")
    names_before = set(q.pending()) | set(q.done())
    pl = plan(spec, root, target_store=store)   # straggler-recovery flow
    assert pl["n_tasks"] == 0                   # nothing new enqueued
    assert pl["skipped_items"] == 4             # 2 completed seeds skipped
    assert set(q.pending()) | set(q.done()) == names_before
    # drain the rest and verify total coverage is exact, not inflated
    run_worker(root, owner="w1")
    assert len(q.done()) == 4
    mg = merge(root, store)
    assert mg["missing_items"] == 0 and mg["target_items"] == 8


def test_read_side_entry_points_reject_missing_queue(tmp_path):
    from repro.fleet.cli import main

    with pytest.raises(ValueError, match="no fleet queue"):
        status(tmp_path / "typo")
    with pytest.raises(ValueError, match="no fleet queue"):
        reap(tmp_path / "typo")
    with pytest.raises(ValueError, match="nothing to merge"):
        merge(tmp_path / "typo", tmp_path / "store")
    # the CLI reports instead of tracebacking — and creates nothing
    assert main(["status", "--root", str(tmp_path / "typo")]) == 1
    assert not (tmp_path / "typo").exists()


def test_run_worker_restores_signal_handlers(tmp_path):
    import signal

    spec = _spec(seeds=(0,))
    root = tmp_path / "fleet"
    plan(spec, root)
    before_term = signal.getsignal(signal.SIGTERM)
    before_int = signal.getsignal(signal.SIGINT)
    run_worker(root, owner="w0")
    assert signal.getsignal(signal.SIGTERM) is before_term
    assert signal.getsignal(signal.SIGINT) is before_int


def test_worker_detects_plan_schema_skew(tmp_path):
    spec = _spec(seeds=(0,))
    root = tmp_path / "fleet"
    plan(spec, root)
    # corrupt a queued task's expected keys (simulates code/version skew)
    q = LeaseQueue(root / "queue")
    name = q.pending()[0]
    doc = json.loads((q.task_dir / f"{name}.json").read_text())
    doc["keys"] = ["not-a-real-item-hash"] * len(doc["keys"])
    (q.task_dir / f"{name}.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="skew"):
        run_worker(root, owner="w0")


def test_merge_verifies_duplicates_bit_for_bit(tmp_path):
    root = tmp_path / "fleet"
    a = SweepStore(worker_store_dir(root, "a"))
    b = SweepStore(worker_store_dir(root, "b"))
    a.add_chunk(["k1", "k2"], np.array([1.5, 2.5]), np.array([0.1, 0.2]),
                metrics={"served": [3.0, 4.0]})
    # duplicate with identical values/metrics but different wall times: OK
    b.add_chunk(["k2"], np.array([2.5]), np.array([9.9]),
                metrics={"served": [4.0]})
    out = merge(root, tmp_path / "merged")
    assert out["merged_items"] == 2 and out["duplicate_items"] == 1
    # conflicting value for an existing item hash: refused loudly
    c = SweepStore(worker_store_dir(root, "c"))
    c.add_chunk(["k1"], np.array([1.5000001]), np.array([0.1]))
    with pytest.raises(FleetMergeConflict, match="bit-for-bit"):
        merge(root, tmp_path / "merged")
    # conflicting metric bytes are also refused
    d = SweepStore(worker_store_dir(tmp_path / "fleet2", "d"))
    d.add_chunk(["k9"], np.array([1.0]), np.array([0.1]),
                metrics={"served": [3.0]})
    e = SweepStore(worker_store_dir(tmp_path / "fleet2", "e"))
    e.add_chunk(["k9"], np.array([1.0]), np.array([0.1]),
                metrics={"served": [4.0]})
    with pytest.raises(FleetMergeConflict, match="metric"):
        merge(tmp_path / "fleet2", tmp_path / "merged2")


def test_task_spec_expands_to_exact_parent_keys(tmp_path):
    spec = _spec(scenarios=("steady", "flash_crowd"),
                 algos=("edf", "fcfs"), seeds=(0, 1, 2))
    root = tmp_path / "fleet"
    plan(spec, root, seeds_per_task=2)
    q = LeaseQueue(root / "queue")
    all_keys = set()
    for name in q.pending():
        task = q.read_task(name)
        sub = task_spec(spec, task)
        assert {it.key() for it in sub.expand()} == set(task.keys)
        all_keys |= set(task.keys)
    assert all_keys == {it.key() for it in spec.expand()}


# ===========================================================================
# The acceptance run: 4 subprocess workers, one SIGKILLed mid-run
# ===========================================================================

def _wait_for_lease(root, timeout=120.0):
    q = LeaseQueue(Path(root) / "queue")
    deadline = time.time() + timeout
    while time.time() < deadline:
        leased = q.leased()
        if leased:
            return leased
        time.sleep(0.05)
    raise AssertionError("no worker claimed a task in time")


def test_fleet_4_workers_one_killed_matches_single_process(
        tmp_path, monkeypatch):
    """The PR invariant: a 4-worker fleet run of a (2 scenario × 2 policy
    × 4 seed) serving grid — one worker SIGKILLed mid-run, its lease
    reaped — merges into a store whose aggregate is byte-identical to the
    single-process run, and pareto on that store does zero replays."""
    spec = _spec(scenarios=("steady", "flash_crowd"),
                 algos=("edf", "fcfs"), seeds=(0, 1, 2, 3))
    ref = run_sweep(spec, store_dir=tmp_path / "ref")

    root = tmp_path / "fleet"
    ttl = 2.0
    pl = plan(spec, root)
    assert pl["n_tasks"] == 16 and pl["n_items"] == 32

    procs = [subprocess.Popen(_worker_cmd(root, f"local-{i}", ttl=ttl),
                              env=_env(), stdout=subprocess.DEVNULL,
                              stderr=subprocess.DEVNULL)
             for i in range(4)]
    try:
        # SIGKILL whichever worker holds the first observed lease —
        # no drain, no release: the crash path the queue exists for
        leased = _wait_for_lease(root)
        q = LeaseQueue(root / "queue")
        doc = json.loads((q.lease_dir / f"{leased[0]}.json").read_text())
        victim = None
        owner = doc.get("lease", {}).get("owner", "")
        for i in range(4):
            if owner == f"local-{i}":
                victim = procs[i]
                break
        if victim is None:
            victim = procs[0]
        victim.kill()
        victim.wait()
        for p in procs:
            if p is not victim:
                assert p.wait(timeout=300) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    # the killed worker's lease expires; reap requeues, a mop-up worker
    # (any worker — here in-process) finishes the chunk
    deadline = time.time() + 4 * ttl
    while time.time() < deadline and LeaseQueue(root / "queue").leased():
        time.sleep(0.1)
        reap(root)
    run_worker(root, owner="mopup")
    st = status(root)
    assert st["queue"]["pending"] == 0 and st["queue"]["leased"] == 0
    assert st["queue"]["done"] == 16

    mg = merge(root, tmp_path / "merged")
    assert mg["missing_items"] == 0
    # duplicates (if the victim had already appended its chunk) were
    # verified bit-for-bit rather than dropped blindly
    assert mg["target_items"] == 32

    got = run_sweep(spec, store_dir=tmp_path / "merged")
    assert got.execution["chunks_computed"] == 0
    for k in ref.values:
        assert ref.values[k].tobytes() == got.values[k].tobytes()

    # schema-v3 store: frontier extraction is a pure store read
    import repro.tuning.pareto as pareto_mod

    def boom(*a, **kw):
        raise AssertionError("pareto replayed a horizon on a v3 store")
    monkeypatch.setattr(pareto_mod, "_replay_metrics", boom)
    frontiers = pareto_mod.frontier_points(tmp_path / "merged")
    assert set(frontiers) == {"steady", "flash_crowd"}
    assert all(len(pts) == 2 for pts in frontiers.values())  # 2 policies


def test_worker_sigterm_is_a_clean_drain(tmp_path):
    """SIGTERM finishes the current task (results + completion land),
    then exits 0 — never an orphaned lease."""
    spec = _spec(seeds=(0, 1, 2, 3))
    root = tmp_path / "fleet"
    plan(spec, root)
    proc = subprocess.Popen(_worker_cmd(root, "term-w"), env=_env(),
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        _wait_for_lease(root)
        proc.terminate()                      # SIGTERM mid-run
        assert proc.wait(timeout=120) == 0    # clean drain exit
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    q = LeaseQueue(root / "queue")
    assert q.leased() == []                   # no lease left behind
    assert len(q.done()) >= 1                 # the in-flight task completed
    # everything marked done really is in the worker's store
    store = SweepStore(worker_store_dir(root, "term-w"))
    for name in q.done():
        task = q.read_task(name)
        assert all(k in store for k in task.keys)


def test_worker_wait_survives_empty_queue_and_drains_on_sigterm(tmp_path):
    """--wait long-polling (elastic fleets): a worker on an empty queue
    stays alive across plan waves instead of exiting "drained", picks up
    newly enqueued tasks, and still honors SIGTERM as a clean drain."""
    spec = _spec(seeds=(0, 1))
    root = tmp_path / "fleet"
    plan(spec, root)
    q = LeaseQueue(root / "queue")
    tasks_dir = root / "queue" / "tasks"
    stash = tmp_path / "stash"
    stash.mkdir()
    # empty the queue before the worker starts: wave 2 hasn't landed yet
    staged = list(tasks_dir.iterdir())
    assert len(staged) == 2
    for p in staged:
        p.rename(stash / p.name)
    proc = subprocess.Popen(
        _worker_cmd(root, "wait-w") + ["--wait", "--poll-interval", "0.1"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    try:
        # several poll periods on an empty queue: a non---wait worker
        # would have exited "drained" long before this
        time.sleep(0.6)
        assert proc.poll() is None
        # the next plan wave arrives (same content the planner would
        # regenerate — task names are pure content hashes)
        for p in list(stash.iterdir()):
            p.rename(tasks_dir / p.name)
        deadline = time.time() + 120
        while len(q.done()) < 2 and time.time() < deadline:
            time.sleep(0.05)
        assert len(q.done()) == 2
        assert proc.poll() is None            # still waiting for wave 3
        proc.terminate()                      # SIGTERM = clean drain
        assert proc.wait(timeout=30) == 0
        out = proc.stdout.read()
        assert "stop=SIGTERM" in out
        assert "2 task(s)" in out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert q.leased() == []                   # nothing orphaned
    store = SweepStore(worker_store_dir(root, "wait-w"))
    for name in q.done():
        assert all(k in store for k in q.read_task(name).keys)


# ===========================================================================
# CLI
# ===========================================================================

def test_fleet_cli_plan_worker_status_merge(tmp_path, capsys):
    from repro.fleet.cli import main

    root, store = tmp_path / "fleet", tmp_path / "store"
    spec_args = ["--kind", "serving", "--scenario", "steady",
                 "--seeds", "0:2", "--ticks", "2", "--algos", "edf"]
    for k, v in {**SMALL, "switching_cost": 0, "stickiness": 0}.items():
        spec_args += ["--override", f"{k}={v}"]
    assert main(["plan", *spec_args, "--root", str(root),
                 "--store", str(store)]) == 0
    assert "planned 2 task(s)" in capsys.readouterr().out

    assert main(["status", "--root", str(root)]) == 0
    assert "2 pending" in capsys.readouterr().out

    assert main(["worker", "--root", str(root), "--owner", "cli-w",
                 "--max-tasks", "1"]) == 0
    assert "1 task(s)" in capsys.readouterr().out
    # merge before the queue drains: partial but honest (exit code 2)
    assert main(["merge", "--root", str(root), "--store", str(store)]) == 2
    assert "still missing" in capsys.readouterr().out

    assert main(["worker", "--root", str(root), "--owner", "cli-w"]) == 0
    capsys.readouterr()
    assert main(["reap", "--root", str(root)]) == 0
    assert main(["merge", "--root", str(root), "--store", str(store)]) == 0
    capsys.readouterr()
    assert len(SweepStore(store)) == 4

    # the merged store resumes as complete under the sweeps CLI
    from repro.sweeps.cli import main as sweeps_main
    rc = sweeps_main(["--kind", "serving", "--scenario", "steady",
                      "--seeds", "0:2", "--ticks", "2", "--algos", "edf",
                      *[a for a in spec_args if "=" in a or
                        a == "--override"],
                      "--out", str(store), "-q"])
    assert rc == 0
    capsys.readouterr()


def test_sweeps_cli_fleet_flag_end_to_end(tmp_path, capsys):
    from repro.sweeps.cli import main as sweeps_main

    args = ["--kind", "serving", "--scenario", "steady", "--seeds", "0:2",
            "--ticks", "2", "--algos", "edf"]
    for k, v in {**SMALL, "switching_cost": 0, "stickiness": 0}.items():
        args += ["--override", f"{k}={v}"]

    ref_store = tmp_path / "ref"
    assert sweeps_main([*args, "--out", str(ref_store), "-q"]) == 0
    fleet_store = tmp_path / "fleet_store"
    assert sweeps_main([*args, "--out", str(fleet_store),
                        "--fleet", "2"]) == 0
    out = capsys.readouterr().out
    assert "merged" in out

    ref, got = SweepStore(ref_store), SweepStore(fleet_store)
    assert set(ref.keys()) == set(got.keys())
    for key in ref.keys():
        a = np.float64(ref.value(key))
        assert a.tobytes() == np.float64(got.value(key)).tobytes()
    # --fleet with --no-store is a usage error
    with pytest.raises(SystemExit):
        sweeps_main([*args, "--no-store", "--fleet", "2"])
    capsys.readouterr()


def test_sweeps_cli_fleet_resumes_extended_seed_range(tmp_path, capsys):
    """Extending --seeds on the same store is the documented resume
    pattern; the fleet path must plan a fresh queue for the extended
    spec (fingerprint-keyed root) and skip already-complete seeds, not
    crash on the old queue's spec fingerprint."""
    from repro.sweeps.cli import main as sweeps_main

    def args(seeds):
        out = ["--kind", "serving", "--scenario", "steady", "--seeds",
               seeds, "--ticks", "2", "--algos", "edf",
               "--out", str(tmp_path / "store"), "-q"]
        for k, v in {**SMALL, "switching_cost": 0, "stickiness": 0}.items():
            out += ["--override", f"{k}={v}"]
        return out

    assert sweeps_main([*args("0:2"), "--fleet", "1"]) == 0
    assert len(SweepStore(tmp_path / "store")) == 4
    assert sweeps_main([*args("0:3"), "--fleet", "1"]) == 0  # extended
    assert len(SweepStore(tmp_path / "store")) == 6
    # complete merges prune their fingerprint-keyed fleet roots — no
    # duplicate result shards accumulate under the store
    fleet_dir = tmp_path / "store" / "fleet"
    assert not fleet_dir.exists() or not list(fleet_dir.iterdir())
    capsys.readouterr()
