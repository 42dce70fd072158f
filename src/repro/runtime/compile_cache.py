"""JAX's persistent compilation cache, as the entry points turn it on.

``chip_smoke.py``, ``benchmarks/run.py`` and ``launch/serve_lp.py`` call
:func:`enable` once before they compile anything; importing the library
sets nothing.  A later process finds a compiled program again only if
it looks in the same directory (the path is part of the cache's key), so
the directory is fixed: the one ``JAX_COMPILATION_CACHE_DIR`` names, or
else :data:`DEFAULT_DIR` inside the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: the
#: checkout's ``.jax_cache/`` (listed in ``.gitignore``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
