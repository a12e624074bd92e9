"""The multi-pod dry-run CLI, end to end (subprocess: it must own jax init
so XLA_FLAGS can force 512 host devices)."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("arch,shape", [("smollm_360m", "prefill_32k")])
def test_dryrun_cli_single_cell(arch, shape, tmp_path):
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "HOME": "/tmp", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", arch, "--shape", shape, "--single-pod"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "[ok]" in proc.stdout

    rec = json.loads(
        (ROOT / "experiments" / "dryrun" /
         f"{arch}__{shape}__pod16x16.json").read_text())
    assert rec["status"] == "ok"
    assert rec["devices"] == 256
    assert rec["corrected"]["flops"] > 0
    assert rec["memory"]["per_device_hbm_bytes"] > 0


def test_dryrun_skip_cell_reported():
    from repro.launch.shapes import cell_plan
    ok, why = cell_plan("hubert_xlarge", "decode_32k")
    assert not ok and "encoder-only" in why
    ok, why = cell_plan("yi_34b", "long_500k")
    assert not ok
    ok, _ = cell_plan("mamba2_2p7b", "long_500k")
    assert ok


def test_input_specs_shapes():
    """input_specs returns allocation-free ShapeDtypeStructs per cell."""
    import jax
    from repro.launch.specs import input_specs

    spec = input_specs("yi_34b", "train_4k")
    assert spec["batch"]["tokens"].shape == (256, 4096)
    assert all(isinstance(v, jax.ShapeDtypeStruct)
               for v in spec["batch"].values())

    spec = input_specs("qwen3_moe_235b", "decode_32k")
    assert spec["token"].shape == (128,)
    assert spec["cache"].kv_k.shape[2] == 32768

    spec = input_specs("mixtral_8x7b", "long_500k")
    assert spec["ring"]  # SWA ⇒ ring buffer bounded at the window
    assert spec["cache"].kv_k.shape[2] == 4096

    spec = input_specs("internvl2_1b", "prefill_32k")
    assert spec["batch"]["patches"].shape[1] == 1024
    assert spec["batch"]["tokens"].shape[1] == 32768 - 1024
