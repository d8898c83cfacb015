"""JAX's persistent compilation cache, placed for the entry points.

The serving engine AOT-compiles one executable per (family, bucket); at
Climber's published depth a cold start compiles every one of them.  Entry
points (``chip_smoke.py``, ``launch/serve.py``, ``flamebench/harness.py``)
call :func:`enable_compile_cache` at start-up; no library module touches
the cache when imported.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise, on an accelerator backend, the cache lives
at one fixed path inside the checkout, :data:`DEFAULT_DIR` (listed in
``.gitignore``): a path built from a temp name, a pid or the time would
never be hit again.  CPU runs compile in seconds and keep no cache (jax
0.9's XLA:CPU logs a machine-feature mismatch error for every cached
executable it loads).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — src/repro/launch/ is three levels below it
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use ("" when
    none is)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return ""
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
