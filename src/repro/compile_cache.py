"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``benchmarks/dsp_experiments.py``,
``python -m repro.fleet``, ``python -m repro.fleet.loadgen``) call
:func:`enable_compile_cache` once before their first compile; importing
this module changes nothing.

The cache key includes the directory, so the path is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX
reads it itself, and nothing is set here), else ``.jax_cache/`` at the
checkout root. It is never derived from a temporary name, a pid or the
time, so a second run of the same checkout finds the first run's entries.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
