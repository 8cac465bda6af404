"""Shared fixtures: the benchmark's cells cut to a size the CPU can run.

Run with ``python -m pytest bench/tests`` from the checkout root.
"""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session", autouse=True)
def _no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def small_cell(name: str, per: int, duration_s: float):
    """The cell as ``BENCHMARK.json`` gives it, with ``per`` scenarios per
    controller and ``duration_s`` of trace."""
    from bench import harness
    cell = copy.deepcopy(harness.load_cell(name))
    cell["config"]["scenarios_per_controller"] = per
    cell["config"]["duration_s"] = duration_s
    return cell
