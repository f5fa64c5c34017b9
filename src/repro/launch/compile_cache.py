"""JAX's persistent compilation cache, kept at one fixed place.

A compiled program is found again only at the path it was written to, so
the path never carries a temp name, a pid or a time. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here; otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored). The CLIs (``repro.launch.serve``, ``repro.launch.server``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`use_compile_cache`
at start-up; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
