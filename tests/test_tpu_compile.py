"""The placement kernels compiled for a described TPU v5e at deployment
widths: each program must hold the Mosaic kernel (``tpu_custom_call``) and
fit one chip's 16 GB. Nothing runs; these compiles catch tiling and VMEM
refusals before any chip time is spent.

The topology is described inside a module fixture (never at import time),
so every test worker collects the same tests and only the worker given
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of the way
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _check(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not compiled"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one chip"


def test_qos_candidates_kernel_compiles_at_1e6_users(one_chip):
    from repro.kernels.qos_matrix.qos_matrix import qos_candidates_pallas

    U, K = 10**6, 10
    users = [_shape(one_chip, (U,))] * 4
    cands = [_shape(one_chip, (U, K))] * 4
    fn = jax.jit(lambda *a: qos_candidates_pallas(*a, delta_max=10.0))
    _check(fn.lower(*users, *cands).compile())


def test_greedy_argmax_kernel_compiles_at_1000_edges(one_chip):
    from repro.kernels.qos_matrix.qos_matrix import greedy_argmax_pallas

    E, P = 1000, 550
    fn = jax.jit(greedy_argmax_pallas)
    _check(fn.lower(_shape(one_chip, (E, P)),
                    _shape(one_chip, (E, P))).compile())


def test_qos_matrix_kernel_compiles_at_1e4_users(one_chip):
    from repro.kernels.qos_matrix.qos_matrix import qos_matrix_pallas

    U, P = 10**4, 550
    users = [_shape(one_chip, (U,))] * 4 + [
        _shape(one_chip, (U,), jnp.int32)]
    models = [_shape(one_chip, (P,))] * 3 + [
        _shape(one_chip, (P,), jnp.int32)]
    fn = jax.jit(lambda *a: qos_matrix_pallas(*a, delta_max=10.0))
    _check(fn.lower(*users, *models).compile())


@pytest.fixture
def chip_branch(monkeypatch):
    """The kernel dispatchers pick interpret mode from
    ``jax.default_backend()``, which is the CPU here: steer them to the
    chip's branch for the compiles of a test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()  # no trace made for the CPU branch may be reused
    yield
    monkeypatch.undo()
    jax.clear_caches()


def test_candidate_build_compiles_with_kernel_at_1e6_users(one_chip,
                                                          chip_branch):
    """The whole top-k candidate build (gathers, kernel, masks). Gathers
    indexed by ``[U, M]`` pairs took minutes to compile at this size."""
    import dataclasses

    from repro.core.candidates import topk_candidates_jnp
    from repro.core.instance import JaxInstance

    U, S, M, P = 10**6, 100, 10, 550
    users = {f: _shape(one_chip, (U,)) for f in
             ("u_alpha", "u_delta", "u_share_k", "u_share_w")}
    models = {f: _shape(one_chip, (P,)) for f in
              ("sm_acc", "sm_k", "sm_w", "sm_r")}
    ji = JaxInstance(**users, **models,
                     u_service=_shape(one_chip, (U,), jnp.int32),
                     u_edge=_shape(one_chip, (U,), jnp.int32),
                     sm_service=_shape(one_chip, (P,), jnp.int32),
                     R=_shape(one_chip, (1000,)),
                     delta_max=_shape(one_chip, ()))

    def build(jinst, table):
        jinst = dataclasses.replace(jinst, delta_max=10.0)  # static
        return topk_candidates_jnp(jinst, table, None, use_kernel=True)

    _check(jax.jit(build).lower(
        ji, _shape(one_chip, (S, M), jnp.int32)).compile())


def test_sparse_egp_tick_compiles_with_kernel_at_1e5_users(one_chip,
                                                          chip_branch):
    """The jitted sparse tick the placement path runs (greedy + σ)."""
    from repro.workloads.batched import sparse_evaluator

    U, E, P, k = 10**5, 100, 550, 10
    _check(sparse_evaluator(P + 1, True).lower(
        _shape(one_chip, (U, k), jnp.int32), _shape(one_chip, (U, k)),
        _shape(one_chip, (U,), jnp.int32),
        _shape(one_chip, (P,), jnp.int32), _shape(one_chip, (P,)),
        _shape(one_chip, (E,))).compile())


def test_placer_step_compiles_with_kernel_at_1e5_users(one_chip, chip_branch):
    """The hysteresis placer's jitted step: the biased greedy, σ and the
    tick's loads and evictions over a resident set."""
    from repro.workloads.batched import placer_program

    U, E, P, k = 10**5, 100, 550, 10
    _check(placer_program(P + 1, True).lower(
        _shape(one_chip, (U, k), jnp.int32), _shape(one_chip, (U, k)),
        _shape(one_chip, (U,), jnp.int32),
        _shape(one_chip, (P,), jnp.int32), _shape(one_chip, (P,)),
        _shape(one_chip, (E,)), _shape(one_chip, (E, P), jnp.bool_),
        _shape(one_chip, ())).compile())
