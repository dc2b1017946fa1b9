"""Where the persistent XLA compile cache lives.

The SPMD engine compiles one program per query shape x capacity tier x
store generation, so a cold start on a chip spends minutes compiling.
``enable_compile_cache`` is the one place an entry point (``chip_smoke.py``,
``python -m repro.serve``, ``benchmarks/run.py``) turns jax's persistent
cache on:

* with ``JAX_COMPILATION_CACHE_DIR`` set, jax reads the directory from
  the environment itself and this module sets no other;
* otherwise the cache goes to ``<checkout>/.jax_cache`` -- one fixed
  path inside the checkout (listed in ``.gitignore``), never one made
  from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the environment variable that places the cache from outside
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the cache directory when the environment does not name one
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
