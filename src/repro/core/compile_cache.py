"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``python -m repro.serve``,
``benchmarks/fftbench.py``) call :func:`enable_compile_cache` from their
``main()``; nothing turns the cache on at import, and tests leave it off.
"""

from __future__ import annotations

import os
from pathlib import Path

#: fixed in-checkout default; the path is part of the cache key, so it
#: must not move between runs (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else :data:`DEFAULT_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
