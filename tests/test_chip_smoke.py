"""chip_smoke.py refuses to report without a TPU, and the compile-cache
helper keeps the cache where the environment or the checkout says."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import ENV_VAR, use_compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    """On the CPU, and in a directory holding only the script, chip_smoke
    exits non-zero and prints no result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = use_compile_cache(), use_compile_cache()
        assert first == second == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
