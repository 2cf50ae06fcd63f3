"""Placement of JAX's persistent compilation cache (``repro.compile_cache``)."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.compile_cache import DEFAULT_CACHE_DIR, setup_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # sets no other


def test_default_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert setup_compile_cache() == DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_CACHE_DIR == str(ROOT / ".jax_cache")


def test_cache_is_written_to_env_dir(tmp_path):
    """A fresh process with the variable set writes its compiles there."""
    code = (
        "import jax\n"
        "from repro.compile_cache import setup_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "setup_compile_cache()\n"
        "jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(tmp_path.iterdir())
