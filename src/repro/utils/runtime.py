"""Process set-up shared by the entry points: where the checkout is, and
JAX's persistent compilation cache.

Nothing here runs at import time.  ``enable_compile_cache`` is called by
the entry points (``chip_smoke.py``, the examples, the benchmarks) and
never by library code, so tests and importers keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[3]     # src/repro/utils/ → root


def checkout_root() -> Path:
    """The repository checkout this package was imported from."""
    return _CHECKOUT


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    its cache directory and nothing is changed.  Otherwise the cache
    lives at the fixed ``<checkout>/.cache/jax_compile`` (git-ignored),
    so every later run from the same checkout finds what earlier runs
    compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = _CHECKOUT / ".cache" / "jax_compile"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
