"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points call `enable_compile_cache()` before anything compiles; no
module does it at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing here overrides it.  Otherwise the cache
goes to ``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed
path, because the directory is part of what a later process must find.
"""

from __future__ import annotations

import importlib.util
import os
from pathlib import Path
from typing import Optional

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the cache on; returns its directory (None without JAX, as in
    the NumPy-only builds, where nothing compiles)."""
    if importlib.util.find_spec("jax") is None:
        return None
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
