"""Where JAX keeps its persistent compilation cache for this checkout."""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory when ``$JAX_COMPILATION_CACHE_DIR`` is unset.  A
#: fixed path inside the checkout (listed in .gitignore), so that a later
#: run finds what an earlier one compiled.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here.  Otherwise the cache goes to ``DEFAULT_DIR``.
    Called from the entry points' ``main``, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
