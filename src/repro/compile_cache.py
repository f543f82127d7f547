"""Where JAX's persistent compilation cache lives — decided in one place.

Entry points that compile (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call ``enable_compile_cache()`` once, before their first
compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing is set
  here.
* unset: the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``). The directory is fixed — never a temp name, a pid or a
  time — so a second run from the same checkout finds what the first one
  compiled.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
