"""CPU rehearsal of ``chip_smoke.py``: every phase of the chip bring-up
run end to end at ``tiny-moe`` size (Pallas kernels in interpret mode),
the script's refusal to run anywhere but on a TPU, and where the entry
points keep their compile cache."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_end_to_end_on_cpu(chip_smoke):
    cfg = get_config("tiny-moe")
    lines = []
    report = chip_smoke.run(cfg, lines.append,
                            requests=((16, 6), (5, 8), (23, 5)),
                            b1_request=(9, 6), max_slots=2, slot_len=48)
    assert report["failed"] == [], "\n".join(lines)
    assert report["serve"]["finished"] == 3
    assert report["serve"]["offload_demand_loads"] > 0
    # CPU programs hold no Pallas custom calls (kernels are interpreted)
    assert set(report["programs"]) == {"embed", "mixer", "moe", "stage",
                                       "head"}
    assert sum(report["programs"].values()) == 0
    # tiny-moe serves in float32: the packed plane matches the float32
    # dequantized reference to float32 accumulation-order noise
    max_abs, max_rel = report["compare"]
    assert max_rel < 1e-4, (max_abs, max_rel)
    assert max(report["kernels"].values()) < 1e-4, report["kernels"]


def test_chip_smoke_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_one_fixed_directory(tmp_path, from_env):
    """Every entry point's compile cache goes to $JAX_COMPILATION_CACHE_DIR
    when that is set, else to ``<checkout>/.jax_cache`` — never a temp
    name, so the next run finds what this one compiled."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = ROOT / ".jax_cache"
    if from_env:
        want = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(7.0))"
            ".block_until_ready()\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(want)] * 2
    assert any(p.name.startswith("jit_") for p in want.iterdir())
