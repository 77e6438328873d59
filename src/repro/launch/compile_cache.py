"""JAX's persistent compilation cache for the entry points.

Called by ``repro.launch.train``'s main, ``benchmarks.run`` and
``chip_smoke.py`` — never on ``import repro``, so library users and the
tests keep whatever cache setting they chose.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the path is part of the cache key, so a directory
that moves from run to run never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
