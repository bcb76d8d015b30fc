"""Where JAX's persistent compilation cache lives.

Entry points (``serve.main``, ``train.main``, ``chip_smoke.py``) call
:func:`configure_compile_cache` before they compile anything; importing
a module never touches the setting.  ``JAX_COMPILATION_CACHE_DIR``, when
set, wins: JAX reads it itself and nothing here overrides it.  Otherwise
the cache is one fixed directory inside the checkout — the path is part
of what a later run must find again, so it is never a temporary name.
Every compile is kept, not only those over JAX's default one-second
floor, so a warm start compiles nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "configure_compile_cache"]

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
