"""Smoke run of the placement and serving path on one TPU chip.

    python chip_smoke.py [--seed N]      # one chip: every phase below
    python chip_smoke.py --chips 4       # four chips: the sharded sweep only

Phases, each through the entry points a user calls, at deployment size:

* placement — the sweeps engine as ``python -m repro.sweeps --validate``
  runs it, checked against the NumPy host path; a §VI-B synthetic tick
  (U = 10⁴, E = 10) on the sparse kernel path and the dense evaluator, and
  against the host at U = 2000; a ``placement_scale`` tick at U = 10⁶,
  E = 1000 with and without the Pallas kernels, whose compiled program
  must hold the Mosaic kernels (``tpu_custom_call``).
* model — ``ModelServer`` serves a few requests with smollm-360m at its
  published widths (random weights from ``--seed``), and prefill plus one
  decode step matches the full forward pass in float32.
* served loop — a virtual-clock gateway replay of ``flash_crowd`` must be
  byte-identical to ``run_horizon``.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits nonzero before any phase runs, and no
phase catches its own failure. Timings printed on the way are smoke
timings from the host clock, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: relative σ agreement between two device paths of the same tick
REL_TOL = 1e-4

SWEEP_SCENARIOS = ("steady", "flash_crowd")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require(ok, detail=None) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(f"smoke check failed: {detail!r}")


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(1.0, abs(b))


# ===========================================================================
# Placement
# ===========================================================================

def sweep_phase(seeds=(0, 1, 2), n_ticks: int = 2) -> float:
    """The sweeps engine on an explicit one-device mesh, validated as
    ``python -m repro.sweeps --validate`` does; returns max |Δσ|."""
    from repro.launch.mesh import make_sweep_mesh
    from repro.sweeps import HOST_PARITY_ATOL, SweepSpec, run_sweep
    from repro.sweeps.cli import max_host_diff

    spec = SweepSpec(scenarios=SWEEP_SCENARIOS, seeds=tuple(seeds),
                     n_ticks=n_ticks, algos=("egp",))
    res = run_sweep(spec, mesh=make_sweep_mesh(1))
    require(res.complete, res.execution)
    require(res.execution["n_devices"] == 1, res.execution)
    worst = max_host_diff(spec, res)
    require(worst <= HOST_PARITY_ATOL, worst)
    log(f"sweep: {len(spec.expand())} items on {res.execution['backend']} "
        f"({res.execution['path']}), max|Δσ| vs host = {worst!r} "
        f"<= {HOST_PARITY_ATOL}")
    return worst


def _sparse_sigma(inst, use_kernel: bool) -> float:
    from repro.workloads import evaluate_sparse

    return float(evaluate_sparse([inst], use_kernel=use_kernel)[0][0])


def paper_tick_phase(n_users: int = 10_000, n_edges: int = 10,
                     host_users: int = 2000, n_services: int = 100,
                     seed: int = 0) -> None:
    """§VI-B synthetic tick: the sparse tick with kernels against the dense
    evaluator at ``n_users``, and both against the host at ``host_users``."""
    from repro.core.instance import synthetic_instance
    from repro.workloads import evaluate_batch, evaluate_host, pad_instances

    for U in (host_users, n_users):
        inst = synthetic_instance(n_users=U, n_edges=n_edges,
                                  n_services=n_services, seed=seed)
        sparse = _sparse_sigma(inst, use_kernel=True)
        values, _ = evaluate_batch(pad_instances([inst]),
                                   max_iters=inst.P + 1)
        dense = float(values[0])
        require(rel_diff(sparse, dense) <= REL_TOL, (U, sparse, dense))
        line = (f"§VI-B tick U={U} E={n_edges} P={inst.P}: "
                f"σ sparse+kernel {sparse!r}, dense {dense!r}")
        if U == host_users:
            host = float(evaluate_host([inst])[0])
            require(rel_diff(sparse, host) <= REL_TOL, (sparse, host))
            require(rel_diff(dense, host) <= REL_TOL, (dense, host))
            line += f", host {host!r}"
        log(line)


def compiled_sparse_tick(inst) -> list:
    """The compiled texts of the two programs of one sparse tick with
    kernels: the candidate build and the greedy with σ."""
    import jax

    from repro.core.candidates import impl_table_np, topk_candidates_jnp
    from repro.workloads.batched import sparse_evaluator

    ji = inst.as_jax()
    table = impl_table_np(inst.sm_service, inst.S)
    delta_max = float(inst.delta_max)  # a static argument of the kernel

    def build(jinst, tbl):
        jinst = dataclasses.replace(jinst, delta_max=delta_max)
        return topk_candidates_jnp(jinst, tbl, None, use_kernel=True)

    cand = jax.jit(build).lower(ji, table).compile()
    cand_idx, cand_q = cand(ji, table)
    tick = sparse_evaluator(inst.P + 1, True).lower(
        cand_idx, cand_q, ji.u_edge, ji.sm_service, ji.sm_r, ji.R).compile()
    return [cand.as_text(), tick.as_text()]


def scale_tick_phase(n_users: int = 10**6, n_edges: int = 1000,
                     n_services: int = 100, seed: int = 0,
                     expect_kernel: bool = True) -> None:
    """A ``placement_scale`` tick with and without the Pallas kernels;
    with ``expect_kernel`` both programs must hold a compiled kernel."""
    from repro.core.instance import synthetic_instance

    inst = synthetic_instance(n_users=n_users, n_edges=n_edges,
                              n_services=n_services, seed=seed)
    sigma, warm_s = {}, {}
    for use_kernel in (True, False):
        _sparse_sigma(inst, use_kernel)  # compile
        t0 = time.perf_counter()
        sigma[use_kernel] = _sparse_sigma(inst, use_kernel)
        warm_s[use_kernel] = time.perf_counter() - t0
    require(rel_diff(sigma[True], sigma[False]) <= REL_TOL, sigma)
    if expect_kernel:
        for text in compiled_sparse_tick(inst):
            require("tpu_custom_call" in text,
                    "a Pallas kernel did not compile for the chip")
    log(f"scale tick U={n_users} E={n_edges} P={inst.P}: σ kernel "
        f"{sigma[True]!r}, reference {sigma[False]!r}; smoke timing of one "
        f"warm tick (host clock, not a benchmark): kernel "
        f"{warm_s[True]!r} s, reference {warm_s[False]!r} s"
        + ("; compiled programs hold tpu_custom_call" if expect_kernel
           else ""))


# ===========================================================================
# Model
# ===========================================================================

def model_phase(cfg, n_requests: int = 4, prompt_len: int = 128,
                new_tokens: int = 16, check_batch: int = 2,
                check_len: int = 128, seed: int = 0) -> float:
    """Serve requests through ``ModelServer``, then check prefill + one
    decode step against the forward pass in float32; returns max |Δlogit|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.serving.engine import ModelServer

    rng = np.random.default_rng(seed)
    server = ModelServer(cfg, bucket_batch=n_requests,
                         bucket_seq=prompt_len + new_tokens, seed=seed)
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, prompt_len))
    server.generate(prompts, n_steps=new_tokens)  # compile
    out, prefill_s, decode_s = server.generate(prompts, n_steps=new_tokens)
    require(out.shape == (n_requests, new_tokens), out.shape)
    require(out.min() >= 0 and out.max() < cfg.vocab_size)
    log(f"model {cfg.name} (layers {cfg.n_layers}, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}): served {n_requests} requests x "
        f"{prompt_len} prompt + {new_tokens} new tokens; smoke timing (host "
        f"clock, not a benchmark): prefill {prefill_s!r} s, decode "
        f"{decode_s!r} s")
    del server

    f32 = cfg.with_(dtype="float32", remat=False)
    params = T.init_params(f32, jax.random.PRNGKey(seed + 1))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (check_batch, check_len)), jnp.int32)
    cache, ring = T.init_cache(f32, check_batch, check_len)

    def full(p, t):
        x = T.forward(p, f32, {"tokens": t})
        return T.logits_fn(p, f32, x[:, -1:], None)[:, 0]

    def incremental(p, t, c):
        _, c = T.prefill(p, f32, {"tokens": t[:, :-1]}, c, ring)
        return T.decode_step(p, f32, t[:, -1], c, ring)[0]

    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(full)(params, tokens))
        got = np.asarray(jax.jit(incremental)(params, tokens, cache))
    require(np.isfinite(want).all() and np.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)
    worst = float(np.abs(got - want).max())
    log(f"model decode vs forward (float32, highest precision, "
        f"{check_batch}x{check_len}): max|Δlogit| = {worst!r}")
    return worst


# ===========================================================================
# Served loop
# ===========================================================================

def served_phase(n_ticks: int = 4, seed: int = 0) -> str:
    """Virtual-clock gateway replay of ``flash_crowd`` against
    ``run_horizon``, as ``python -m repro.gateway replay`` checks it.

    The served loop is host NumPy today (``DynamicPlacer`` does not call
    the device greedy), so this phase shows that the loop runs on the
    chip's host, not on the chip."""
    from repro.gateway.cli import replay_live
    from repro.gateway.control import result_digest
    from repro.serving.horizon import HorizonConfig, run_horizon

    hconfig = HorizonConfig(scenario="flash_crowd", policy="feedback",
                            seed=seed, n_ticks=n_ticks)
    live = result_digest(replay_live(hconfig))
    offline = result_digest(run_horizon(hconfig))
    require(live == offline, (live, offline))
    log(f"served loop (host NumPy, not on the chip): flash_crowd seed "
        f"{seed}, {n_ticks} ticks, gateway replay digest {live} == "
        f"run_horizon")
    return live


# ===========================================================================
# Four chips: the sharded sweep
# ===========================================================================

def sharded_sweep_phase(n_devices: int = 4) -> None:
    """``run_sweep`` on an ``n_devices`` ``shard_map`` mesh against a
    one-device mesh in the same process: values must be bit-identical."""
    import numpy as np

    from repro.launch.mesh import make_sweep_mesh
    from repro.sweeps import SweepSpec, run_sweep

    spec = SweepSpec(scenarios=SWEEP_SCENARIOS, seeds=(0, 1, 2), n_ticks=2,
                     algos=("egp",))
    # chunk_size=5 over 6 items per group: an uneven chunk of 5 (padded to
    # 8 on 4 devices) and a chunk of 1 (padded to 4)
    sharded = run_sweep(spec, chunk_size=5, mesh=make_sweep_mesh())
    require(sharded.execution["path"] == "shard_map", sharded.execution)
    require(sharded.execution["n_devices"] == n_devices, sharded.execution)
    single = run_sweep(spec, chunk_size=5, mesh=make_sweep_mesh(1))
    require(single.execution["path"] == "vmap", single.execution)
    for key, values in single.values.items():
        np.testing.assert_array_equal(sharded.values[key], values)
    log(f"sharded sweep: {len(spec.expand())} items, shard_map over "
        f"{n_devices} {sharded.execution['backend']} devices bit-identical "
        f"to one device")


# ===========================================================================

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep across four chips")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); no phase was run", file=sys.stderr)
        return 1
    if args.chips == 4:
        if len(devices) != 4:
            print(f"chip_smoke: --chips 4 needs 4 devices, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        sharded_sweep_phase(n_devices=4)
    else:
        from repro.configs import get_config

        with jax.default_device(devices[0]):
            sweep_phase()
            paper_tick_phase(seed=args.seed)
            scale_tick_phase(seed=args.seed)
            model_phase(get_config("smollm_360m"), seed=args.seed)
            served_phase(seed=args.seed)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
