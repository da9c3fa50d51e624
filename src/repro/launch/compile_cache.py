"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the path it was written to,
so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX then reads it itself), else ``.jax_cache/`` at
the root of the checkout.  Entry points call :func:`enable_compile_cache`
at the start of ``main()``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory the rule above picks."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`cache_dir`; returns it."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
