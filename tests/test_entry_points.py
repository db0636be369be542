"""Entry points: chip_smoke.py refuses the CPU, the CLIs fail loudly instead
of falling back, and the compile cache lands where it is told."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=300, env={**os.environ, "PYTHONPATH": str(SRC), **env})


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """No CPU fallback: with JAX held to the CPU, or copied out of the repo,
    the smoke exits non-zero and prints no result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    proc = _run([str(script)], cwd=script.parent, JAX_PLATFORMS="cpu",
                PYTHONPATH="")
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert "chip smoke failed" in proc.stderr


def test_train_pod_parallel_on_host_mesh_raises():
    from repro.launch.train import main
    with pytest.raises(ValueError, match="pod"):
        main(["--reduced", "--pod-parallel", "--compress", "--steps", "1"])


def test_serve_cli_defaults_to_published_widths():
    from repro.configs import get_config
    from repro.launch.serve import config_for, parse_args
    cfg = config_for(parse_args([]))
    assert cfg == get_config("granite-3-2b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.vocab_size) == (40, 2048, 32, 8, 49155)
    assert config_for(parse_args(["--reduced"])) == cfg.reduced()


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(16)).block_until_ready()
"""


def test_compile_cache_uses_the_env_dir(tmp_path):
    cache = tmp_path / "cc"
    proc = _run(["-c", _CACHE_PROBE.format(compile=True)], cwd=tmp_path,
                JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(cache)] * 2
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=False)],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**env, "PYTHONPATH": str(SRC), "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(ROOT / ".jax_cache")] * 2
