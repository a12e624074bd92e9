"""chip_smoke.py: every phase at toy sizes on the CPU (kernels in interpret
mode), the four-chip comparison on four virtual CPU devices, and the
refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the child must never take an accelerator
    return env


def test_sweep_phase_matches_host(smoke):
    from repro.sweeps import HOST_PARITY_ATOL

    assert smoke.sweep_phase(seeds=(0,), n_ticks=1) <= HOST_PARITY_ATOL


def test_paper_tick_phase_sparse_dense_host_agree(smoke):
    smoke.paper_tick_phase(n_users=400, n_edges=4, host_users=150,
                           n_services=12)


def test_scale_tick_phase_kernel_matches_reference(smoke):
    smoke.scale_tick_phase(n_users=600, n_edges=8, n_services=12,
                           expect_kernel=False)


def test_compiled_sparse_tick_runs_interpreted_off_tpu(smoke):
    """Off the chip the dispatchers interpret the kernels, so the compiled
    texts the smoke inspects hold no Mosaic call: the check can fail."""
    from repro.core.instance import synthetic_instance

    inst = synthetic_instance(n_users=64, n_edges=3, n_services=6, seed=1)
    texts = smoke.compiled_sparse_tick(inst)
    assert len(texts) == 2
    assert not any("tpu_custom_call" in t for t in texts)


def test_model_phase_decode_matches_forward(smoke):
    from repro.configs import get_smoke_config

    worst = smoke.model_phase(get_smoke_config("smollm_360m"), n_requests=2,
                              prompt_len=8, new_tokens=3, check_len=8)
    assert worst < 2e-3


def test_served_phase_replay_equals_run_horizon(smoke):
    assert len(smoke.served_phase(n_ticks=2)) > 0


def test_four_device_comparison_on_virtual_cpus():
    code = ("import sys; sys.path.insert(0, {root!r}); import chip_smoke; "
            "chip_smoke.sharded_sweep_phase(n_devices=4)").format(
                root=str(ROOT))
    env = _cpu_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "shard_map over 4 cpu devices bit-identical" in proc.stdout


def test_no_tpu_exits_nonzero_without_ok_line():
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=_cpu_env(), cwd=ROOT, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "no phase was run" in proc.stderr
    assert '"ok"' not in proc.stdout and "[smoke]" not in proc.stdout


# ===========================================================================
# The compile cache helper the entry points call
# ===========================================================================

def _run_py(code, env):
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()[-1]


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    env = _cpu_env()
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "where = enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
            "print(where)\n")
    assert _run_py(code, env) == str(tmp_path / "cc")
    assert any((tmp_path / "cc").iterdir())


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout():
    env = _cpu_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = ("import jax\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    assert _run_py(code, env) == str(ROOT / ".jax_cache")
