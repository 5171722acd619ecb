"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``repro.launch.quantize``, ``repro.launch.serve``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`setup_compile_cache`
from ``main()`` — never at import, so importing the package leaves JAX's
configuration alone.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at the fixed,
git-ignored ``<checkout>/.jax_cache``: the directory is part of the cache
key, so a path derived from a temporary name, a pid or the time would never
hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
