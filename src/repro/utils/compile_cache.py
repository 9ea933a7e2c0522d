"""Where JAX keeps its persistent compilation cache.

A cache hit needs the same path every time (the path is part of the key), so
the directory is either the one ``JAX_COMPILATION_CACHE_DIR`` names — JAX
reads that variable itself, and nothing here overrides it — or one fixed,
gitignored directory inside the checkout. Never a temp, pid or time-derived
path.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory. Call before the first compile."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
