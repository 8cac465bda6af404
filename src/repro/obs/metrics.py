"""Counters and gauges for the sweep stack.

All mutation helpers (:func:`inc`, :func:`set_gauge`,
:func:`add_phase`, :func:`track_jit_cache`) are no-ops while obs is
disabled — one module-level bool check, mirroring ``trace.span``.  The
registry itself is always importable and inspectable so exporters and
tests can read a snapshot without flipping the global flag.

Naming conventions (see docs/OBSERVABILITY.md):

* dotted lowercase names, most-general prefix first:
  ``sweep.ticks``, ``transfer.h2d_bytes``, ``recompiles.fused_scan``,
  ``phase.simulate_wall_s``.
* per-phase walls are plain float counters named ``phase.<name>_wall_s``
  with ``<name>`` in {simulate, forecast, detect, fit, acquire}.
* recompile counters are derived from jit dispatch-cache growth — the
  same ``_cache_size()`` signal ``analysis.contracts.count_traces`` uses.
  The cache is process-wide, so the counter measures growth since the
  previous sample, not absolute size.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Union

from . import trace as _trace

__all__ = [
    "Counter", "Gauge", "MetricsRegistry", "registry",
    "inc", "set_gauge", "add_phase", "track_jit_cache",
    "jit_cache_size", "snapshot", "clear", "PHASES",
]

PHASES = ("simulate", "forecast", "detect", "fit", "acquire")

Num = Union[int, float]


class Counter:
    """Monotonically increasing numeric metric (int or float)."""
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value: Num = 0

    def inc(self, n: Num = 1) -> None:
        self.value += n


class Gauge:
    """Last-value metric."""
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value: Optional[Num] = None

    def set(self, v: Num) -> None:
        self.value = v


class MetricsRegistry:
    def __init__(self) -> None:
        self._metrics: Dict[str, Any] = {}

    def counter(self, name: str) -> Counter:
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = Counter(name)
        return m

    def gauge(self, name: str) -> Gauge:
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = Gauge(name)
        return m

    def snapshot(self) -> Dict[str, Any]:
        """Flat, JSON-ready view: ``{"counters": {...}, "gauges": {...}}``."""
        counters: Dict[str, Num] = {}
        gauges: Dict[str, Num] = {}
        for name, m in sorted(self._metrics.items()):
            if isinstance(m, Counter):
                counters[name] = m.value
            elif m.value is not None:
                gauges[name] = m.value
        return {"counters": counters, "gauges": gauges}

    def clear(self) -> None:
        self._metrics.clear()


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REGISTRY


def inc(name: str, n: Num = 1) -> None:
    if not _trace._ENABLED:
        return
    _REGISTRY.counter(name).inc(n)


def set_gauge(name: str, v: Num) -> None:
    if not _trace._ENABLED:
        return
    _REGISTRY.gauge(name).set(v)


def add_phase(phase: str, wall_s: float) -> None:
    """Accumulate into the per-phase wall counter
    ``phase.<phase>_wall_s``."""
    if not _trace._ENABLED:
        return
    _REGISTRY.counter(f"phase.{phase}_wall_s").inc(float(wall_s))


def jit_cache_size(fns: Sequence[Any]) -> int:
    """Sum of jit dispatch-cache sizes over ``fns`` (0 for non-jitted
    entries).  Growth between two samples == number of fresh traces, the
    same signal ``analysis.contracts.count_traces`` measures."""
    total = 0
    for fn in fns:
        size = getattr(fn, "_cache_size", None)
        if size is not None:
            total += int(size())
    return total


def track_jit_cache(name: str, size: int) -> None:
    """Record jit-cache growth for ``name``: bumps the counter
    ``recompiles.<name>`` by the delta since the last sample and keeps
    the absolute size in the gauge ``jit_cache.<name>``."""
    if not _trace._ENABLED:
        return
    g = _REGISTRY.gauge(f"jit_cache.{name}")
    prev = g.value or 0
    if size > prev:
        _REGISTRY.counter(f"recompiles.{name}").inc(size - prev)
    g.set(size)


def snapshot() -> Dict[str, Any]:
    return _REGISTRY.snapshot()


def clear() -> None:
    _REGISTRY.clear()
