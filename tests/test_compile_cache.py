"""The entry points' persistent compile cache (launch/compile_cache.py)."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


def test_without_env_cache_goes_to_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert compile_cache.REPO_CACHE_DIR == ROOT / ".jax_cache"


def test_env_dir_holds_the_entries_and_nothing_is_set(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing, and a
    compile lands there and not in the repo's directory."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n")
    repo_dir = compile_cache.REPO_CACHE_DIR
    before = set(os.listdir(repo_dir)) if repo_dir.exists() else set()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)
    assert any(name.startswith("jit_") for name in os.listdir(tmp_path))
    after = set(os.listdir(repo_dir)) if repo_dir.exists() else set()
    assert after == before
