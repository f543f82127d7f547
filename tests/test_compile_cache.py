"""Where JAX's persistent compilation cache lands (repro.compile_cache)."""
import os
import pathlib
import subprocess
import sys

import jax

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_checkout_cache_is_fixed_inside_the_checkout():
    assert compile_cache.CHECKOUT_CACHE == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_points_jax_at_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compiles_land_in_the_env_directory(tmp_path):
    """End to end, in a fresh process (JAX reads the variable at start-up):
    a compile after ``enable_compile_cache`` is written where
    ``JAX_COMPILATION_CACHE_DIR`` points."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(3)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"),
               **{compile_cache.ENV_VAR: str(tmp_path)})
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())
