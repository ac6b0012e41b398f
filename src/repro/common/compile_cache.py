"""JAX's persistent compilation cache, placed from outside or at one fixed
path inside the checkout.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module sets
no other path.  Unset: programs compiled for an accelerator are cached at
``<checkout>/.jax_cache`` (listed in ``.gitignore``); CPU programs are not
cached, since they compile in seconds and an executable cached on one host
may use CPU features another host lacks.  The path is part of the cache's
key, so it is never built from a temp name, a pid or the time.  Entry
points call :func:`enable` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/common/compile_cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> Optional[str]:
    """Turn the persistent cache on; returns its directory (None: off)."""
    import jax

    path = os.environ.get(ENV_VAR)
    if path:
        return path
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
