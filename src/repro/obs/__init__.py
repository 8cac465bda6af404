"""repro.obs — sweep-wide tracing + metrics (see docs/OBSERVABILITY.md).

Host-side, opt-in observability for the sweep stack:

* :mod:`repro.obs.trace` — nestable spans with monotonic ns timestamps
  and a hard zero-cost no-op path while disabled;
* :mod:`repro.obs.metrics` — counters and gauges plus jit-cache
  recompile tracking;
* :mod:`repro.obs.export` — Chrome-trace (Perfetto) JSON and the
  schema-versioned ``BENCH_sweep.json`` perf-trajectory format;
* :mod:`repro.obs.probe` — CompilationContract probes proving the
  instrumentation adds zero ops to compiled HLO.

Everything is off by default; ``obs.enable()`` flips one module-level
flag.  Results are bit-identical either way — instrumentation only ever
*times* the host side of the dispatch boundary (pinned by the obs
contract probes and the four-way differential in
``tests/helpers/sharded_diff.py``).
"""
from __future__ import annotations

import time
from typing import Any

from . import export, metrics, probe, trace
from .export import (BENCH_SCHEMA, TRACE_SCHEMA, chrome_trace, diff_bench,
                     format_diff, leg_key, load_bench, make_bench, make_leg,
                     merge_bench, write_chrome_trace)
from .metrics import (add_phase, inc, jit_cache_size, registry, set_gauge,
                      snapshot, track_jit_cache)
from .probe import instrumentation_probe
from .trace import (disable, enable, enabled, force_disabled, force_enabled,
                    span, tracer)

__all__ = [
    "trace", "metrics", "export", "probe",
    "span", "tracer", "enable", "disable", "enabled",
    "force_enabled", "force_disabled",
    "inc", "set_gauge", "add_phase", "track_jit_cache",
    "jit_cache_size", "registry", "snapshot",
    "chrome_trace", "write_chrome_trace", "make_leg", "make_bench",
    "merge_bench", "load_bench", "diff_bench", "format_diff", "leg_key",
    "BENCH_SCHEMA", "TRACE_SCHEMA",
    "instrumentation_probe", "timed_phase", "reset",
]


class _NullTimedPhase:
    __slots__ = ()

    def __enter__(self) -> "_NullTimedPhase":
        return self

    def __exit__(self, *exc: object) -> None:
        return None


_NULL_TIMED_PHASE = _NullTimedPhase()


class _TimedPhase:
    """Span + per-phase wall counter in one context manager."""
    __slots__ = ("_phase", "_span", "_t0")

    def __init__(self, phase: str, name: str, attrs: dict):
        self._phase = phase
        self._span = trace.tracer().span(name, attrs)
        self._t0 = 0.0

    def __enter__(self) -> "_TimedPhase":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: object) -> None:
        wall = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        metrics.add_phase(self._phase, wall)


def timed_phase(phase: str, name: str, **attrs: Any):
    """Open span ``name`` and accumulate its wall into
    ``phase.<phase>_wall_s``.  No-op singleton while obs is disabled."""
    if not trace._ENABLED:
        return _NULL_TIMED_PHASE
    return _TimedPhase(phase, name, attrs)


def reset() -> None:
    """Clear collected spans and metrics (the enabled flag is untouched)."""
    trace.tracer().clear()
    metrics.clear()
