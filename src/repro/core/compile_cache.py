"""JAX's persistent compilation cache, at a path a later run finds again.

Every process that compiles for the device calls
:func:`enable_compile_cache` once, from its entry point (the launch and
benchmark ``main()``s, ``chip_smoke.py``, the fleet's ``worker_main``).
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing here changes it.  Otherwise the cache goes to one fixed
directory inside the checkout: the directory is part of what a cached
entry is found under, so a path built from a temporary name, a pid or
the time would never hit.

Importing a module never turns the cache on; tests turn it off for
themselves and their child processes (``tests/conftest.py``).
"""
from __future__ import annotations

import os
from pathlib import Path

from repro.core import REPO_ROOT

#: the in-checkout default (listed in ``.gitignore``)
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Return the directory of this process's persistent compilation
    cache, pointing JAX at :data:`DEFAULT_CACHE_DIR` unless
    ``JAX_COMPILATION_CACHE_DIR`` names one.  Starts no backend, so a
    process that must stay off the device may call it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR
