"""JAX's persistent compilation cache at a fixed, placeable directory.

A compiled program is found again only where it was written, so the cache
directory must not move between runs: never a temp name, a pid or a time.
Entry points call :func:`enable_compile_cache` before their first compile
(the ``repro-deploy`` CLI, ``benchmarks/run.py``, ``chip_smoke.py``); library
import never does.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout this package was loaded from (``<checkout>/src/repro``)
_CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str | None:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing else is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache``. An installed (non-checkout) package has no
    such place, so there the cache stays off (returns None).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not (_CHECKOUT / "pyproject.toml").is_file():
        return None
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
