"""JAX persistent compilation cache, placed from outside or at one fixed
directory in the checkout.

Called by the entry points (``launch.serve``, ``launch.compress``,
``launch.train``, ``chip_smoke.py``) before their first compile, never at
import. A chip run that starts cold spends minutes compiling the model's
step programs; a cache at a fixed path lets the next process on the same
machine skip that. The path is part of JAX's cache key, so it never holds a
temp name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``.jax_cache/`` at the repo root (listed in ``.gitignore``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own reading of the
    variable governs and nothing is overridden; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
