"""Where JAX keeps its persistent compilation cache.

The 1M-replica and E=2^20 programs take tens of seconds each to compile
for the chip, so every entry point that drives the device (the CLI,
``bench.py``, ``chip_smoke.py``) places the cache at start-up with
``place_compile_cache``.  It is never called at import, by the test
harness, or by tests that only drive the library.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
overrides it.  Unset: one fixed directory inside the checkout (listed
in ``.gitignore``).  The path is part of each entry's key, so it is
never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
