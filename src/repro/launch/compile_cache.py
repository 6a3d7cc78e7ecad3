"""JAX's persistent compilation cache, placed from outside or at one
fixed directory in the checkout.

Entry points (``launch/serve.py``, the benchmarks, ``chip_smoke.py``)
call :func:`enable_compile_cache` once at start-up; importing this module
changes nothing.  The directory is part of the cache key, so it never
carries a temp name, a pid or a timestamp: a run that compiles the
packed per-layer programs afresh finds them again the next time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
