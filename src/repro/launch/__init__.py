"""launch subsystem."""

from __future__ import annotations

import os
from pathlib import Path


def use_compile_cache(default_dir: Path) -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to ``default_dir``, a
    fixed path, so that a later run finds what an earlier one compiled;
    a temporary or per-process path would never hit. Returns the
    directory used.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(default_dir))
    return str(default_dir)
