"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.fl_train``, the examples) call ``enable_compile_cache()``
at the start of ``main``; nothing turns the cache on at import.

Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when it is set —
JAX reads that variable itself, so no other path is set in code — and
otherwise a fixed ``.jax_cache/`` at the root of the checkout. The path
never depends on a temporary name, a process id or the time: a new
directory for every run would never be hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory the rule above picks."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()`` and
    return it. A cache turned off (``JAX_ENABLE_COMPILATION_CACHE=0``)
    stays off."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
