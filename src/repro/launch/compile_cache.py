"""Where JAX keeps its persistent compilation cache.

The launchers and ``chip_smoke.py`` call `use_compile_cache` before their
first compile.  A cache is only found again at the path it was written
to, so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads that variable itself, and nothing is set here), otherwise
``.jax_cache`` at the root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
