"""chip_smoke.py: its phases at small sizes on the CPU, and its refusal to
run without a TPU.  The script itself runs only on the chip; these tests
keep its phases working as the engine changes."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke
from repro import compile_cache

pytestmark = pytest.mark.tier1

ROOT = Path(chip_smoke.__file__).resolve().parent


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("phase,kwargs", [
    ("phase_fig9_10", dict(n_hosts=100, n_vms=5, n_groups=4)),
    ("phase_table1", {}),
    ("phase_fig7_8", dict(n_hosts=1000)),
    ("phase_skippable", {}),
    ("phase_batch_major", dict(b=16)),
    ("phase_campaign", dict(n=64, chunk=32)),
    ("phase_sharded_campaign", dict(n=64, chunk=32, n_devices=1)),
])
def test_phase_passes_at_small_size(phase, kwargs, capsys):
    getattr(chip_smoke, phase)(**kwargs)
    assert "compile_s=" in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the cache
    goes to the fixed ``.jax_cache/`` at the root of the checkout."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
