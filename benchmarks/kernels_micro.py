"""Kernel microbenchmarks (host wall-time; interpret-mode kernels on CPU
validate correctness — TPU timing comes from the roofline model, since the
container has no TPU). Emits name,us_per_call,derived CSV rows."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(verbose: bool = True):
    rows = []
    # --- qos matrix: jnp ref vs numpy core (control-plane throughput) -----
    from repro.core import synthetic_instance, qos_matrix_np, qos_matrix_jnp
    from repro.kernels.qos_matrix.ref import qos_matrix_ref
    inst = synthetic_instance(2000, seed=0)
    ji = inst.as_jax()
    t_np = _time(lambda: qos_matrix_np(inst))
    f_jnp = jax.jit(qos_matrix_jnp)
    t_jnp = _time(f_jnp, ji)
    UP = inst.U * inst.P
    rows.append(("qos_matrix_numpy", t_np, f"{UP/t_np:.0f} pairs/us"))
    rows.append(("qos_matrix_jnp_jit", t_jnp, f"{UP/t_jnp:.0f} pairs/us"))

    # --- qos matrix Pallas dispatcher, timed via repro.obs span durations ---
    # block_until_ready inside the span: JAX dispatch is async, so a span
    # around the call alone would cover dispatch, not compute
    from repro import obs
    from repro.obs import trace as _obs_trace
    from repro.kernels.qos_matrix.ops import qos_matrix_from_instance
    small = synthetic_instance(256, seed=0)
    sji = small.as_jax()
    prev = obs.get_tracer()
    tr = obs.enable(capacity=256)
    try:
        for _ in range(2):  # warmup (first call pays the XLA compile)
            jax.block_until_ready(qos_matrix_from_instance(sji))
        for _ in range(5):
            with obs.span("bench.qos_matrix_pallas"):
                jax.block_until_ready(qos_matrix_from_instance(sji))
        durs = tr.span_durations_s("bench.qos_matrix_pallas")
        t_k = float(np.mean(durs)) * 1e6
        up_small = small.U * small.P
        rows.append(("qos_matrix_pallas", t_k,
                     f"{up_small/t_k:.0f} pairs/us obs-span "
                     f"(interpret off-TPU)"))
    finally:
        _obs_trace._TRACER = prev  # restore whatever tracer the caller had

    # --- segmented candidate kernels (sparse placement path) ----------------
    from repro.core.candidates import impl_table_np, max_impls_of
    from repro.kernels.qos_matrix.ops import (greedy_argmax,
                                              qos_candidates_from_instance)
    table = jnp.asarray(impl_table_np(np.asarray(small.sm_service), small.S))
    kM = max_impls_of(small)
    for use_kernel, tag in ((False, "jnp_ref"), (True, "pallas_interp")):
        f = lambda: qos_candidates_from_instance(sji, table,
                                                 use_kernel=use_kernel)
        t = _time(lambda: f()[1])
        rows.append((f"qos_candidates_{tag}", t,
                     f"{small.U * kM / t:.0f} pairs/us U={small.U} k={kM}"))
    E, P = 64, small.P
    rng_g = np.random.default_rng(1)
    v = jnp.asarray(rng_g.normal(size=(E, P)), jnp.float32)
    m = jnp.asarray(rng_g.random((E, P)) < 0.5)
    for use_kernel, tag in ((False, "jnp_ref"), (True, "pallas_interp")):
        t = _time(lambda: greedy_argmax(v, m, use_kernel=use_kernel)[1])
        rows.append((f"greedy_argmax_{tag}", t, f"rows/us {E/t:.2f} E={E}"))

    # --- placement algorithms (paper control plane) -------------------------
    from repro.core import egp_np, agp_np, opt_np, qos_matrix_np as qmn
    Q = qmn(inst)
    rows.append(("egp_place_u2000", _time(lambda: egp_np(inst, Q), iters=3),
                 "host"))
    rows.append(("agp_place_u2000", _time(lambda: agp_np(inst, Q), iters=3),
                 "host"))

    # --- flash attention ref (jnp path used by the dry-run) -----------------
    from repro.kernels.flash_attention.ref import attention_ref
    rng = np.random.default_rng(0)
    B, S, Hq, Hkv, hd = 1, 512, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, Hq, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.bfloat16)
    fa = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    t = _time(fa, q, k, v, iters=3)
    fl = 4 * B * Hq * S * S * hd / 2
    rows.append(("attention_ref_512", t, f"{fl/t/1e6:.2f} GFLOP/s host"))

    # --- ssd ref -------------------------------------------------------------
    from repro.models.layers import ssd_chunked
    B, L, H, P, N = 1, 1024, 8, 64, 64
    x = jnp.asarray(rng.normal(size=(B, L, H, P)), jnp.float32)
    dtA = -jnp.asarray(rng.uniform(0.01, 0.4, (B, L, H)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    f = jax.jit(lambda *a: ssd_chunked(*a, chunk=128))
    t = _time(f, x, dtA, bm, cm, iters=3)
    rows.append(("ssd_chunked_1024", t, "host"))

    if verbose:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    run()
