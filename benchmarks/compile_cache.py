"""JAX's persistent compilation cache, for the repo's entry points.

``benchmarks/run.py`` and ``chip_smoke.py`` call ``enable_compile_cache``
before their first compile; nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache`` — a
    fixed path, since the directory is part of every entry's key.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO_ROOT / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    # the data-plane kernels compile in well under the 1 s default floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
