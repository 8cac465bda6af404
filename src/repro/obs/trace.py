"""Span-based host-side tracer with a hard zero-cost disabled path.

Design constraints (see docs/OBSERVABILITY.md):

* Spans live strictly on the *host* side of the jit boundary.  Opening a
  span never creates jax values, never calls into the runtime, and never
  changes what gets traced or compiled — the obs contract probes in
  ``repro.dsp.fused`` / ``repro.dsp.executor`` pin this by comparing
  primitive counts with instrumentation forced on vs. off.
* When tracing is disabled (the default) ``span(...)`` is one module-level
  bool check followed by returning a shared no-op singleton: no allocation,
  no timestamps, no attribute dict materialization (``**attrs`` packing of
  literal kwargs is the only residual cost at a call site).
* Timestamps are ``time.perf_counter_ns()`` — monotonic, ns resolution —
  recorded relative to the tracer's epoch so exported traces start at 0.

The tracer is a process-global singleton (sweeps are single-threaded; the
multi-device engines shard *data*, not the event loop).  Nesting depth is
tracked with an explicit stack so exporters can reconstruct the hierarchy
without relying on timestamp containment.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

__all__ = [
    "SpanRecord", "Tracer", "tracer", "span", "enable", "disable",
    "enabled", "force_enabled", "force_disabled",
]

# Module-level flag checked on every span() call.  Kept as a plain bool
# (not an attribute lookup chain) so the disabled path is as close to free
# as Python allows.
_ENABLED: bool = False
_JAX_PROFILER: bool = False

# Cap on retained span records; beyond it spans are timed but dropped, and
# the drop count is reported so truncation is never silent.
DEFAULT_MAX_EVENTS = 500_000


@dataclass
class SpanRecord:
    """One finished span. Timestamps are ns since the tracer epoch."""
    name: str
    ts_ns: int
    dur_ns: int
    depth: int
    attrs: Dict[str, Any] = field(default_factory=dict)


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: object) -> None:
        return None

    def set_attr(self, key: str, value: Any) -> None:
        return None


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "attrs", "_t0", "_depth", "_annot")

    def __init__(self, tr: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tr
        self.name = name
        self.attrs = attrs
        self._t0 = 0
        self._depth = 0
        self._annot: Any = None

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def __enter__(self) -> "_Span":
        tr = self._tracer
        self._depth = len(tr._stack)
        tr._stack.append(self)
        if _JAX_PROFILER:  # optional device-trace bridge
            annot = _trace_annotation(self.name)
            if annot is not None:
                annot.__enter__()
                self._annot = annot
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: object) -> None:
        # The annotation closes first: its exit can run deferred work
        # (jaxlib frees Python objects released off the interpreter lock)
        # before it stamps its end, and both clocks should count that.
        if self._annot is not None:
            self._annot.__exit__(*exc)
        t1 = time.perf_counter_ns()
        tr = self._tracer
        if tr._stack and tr._stack[-1] is self:
            tr._stack.pop()
        tr._record(SpanRecord(self.name, self._t0 - tr.epoch_ns,
                              t1 - self._t0, self._depth, self.attrs))


def _trace_annotation(name: str) -> Optional[Any]:
    """Best-effort ``jax.profiler.TraceAnnotation`` so device-side traces
    nest under our host spans when a jax profile is being captured."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)
    except Exception:
        return None


class Tracer:
    """Collects finished :class:`SpanRecord`s; exported by obs.export."""

    def __init__(self, max_events: int = DEFAULT_MAX_EVENTS):
        self.epoch_ns = time.perf_counter_ns()
        self.max_events = max_events
        self.events: List[SpanRecord] = []
        self.dropped = 0
        self._stack: List[_Span] = []

    def span(self, name: str, attrs: Dict[str, Any]) -> _Span:
        return _Span(self, name, attrs)

    def _record(self, rec: SpanRecord) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(rec)

    def clear(self) -> None:
        self.epoch_ns = time.perf_counter_ns()
        self.events.clear()
        self.dropped = 0
        self._stack.clear()


_TRACER = Tracer()


def tracer() -> Tracer:
    """The process-global tracer (valid whether or not tracing is on)."""
    return _TRACER


def span(name: str, **attrs: Any):
    """Open a nestable host-side span.

    Usage::

        with obs.span("engine.fused.interval", K=K):
            ...

    Returns a shared no-op singleton when tracing is disabled.
    """
    if not _ENABLED:
        return _NULL_SPAN
    return _TRACER.span(name, attrs)


def enable(*, jax_profiler: bool = False, clear: bool = False) -> None:
    """Turn tracing + metrics on.  ``jax_profiler=True`` additionally
    wraps each span in a ``jax.profiler.TraceAnnotation`` so device traces
    captured by ``jax.profiler`` nest under the host spans."""
    global _ENABLED, _JAX_PROFILER
    if clear:
        _TRACER.clear()
    _JAX_PROFILER = bool(jax_profiler)
    _ENABLED = True


def disable() -> None:
    global _ENABLED, _JAX_PROFILER
    _ENABLED = False
    _JAX_PROFILER = False


def enabled() -> bool:
    return _ENABLED


class _EnabledScope:
    """Context manager forcing the enabled flag to a value, restoring the
    previous state on exit.  Used by tests and by the obs contract probes
    (which trace the compiled functions with instrumentation forced *on*
    to prove it injects zero ops)."""
    __slots__ = ("_target", "_prev")

    def __init__(self, target: bool):
        self._target = target
        self._prev = False

    def __enter__(self) -> "_EnabledScope":
        global _ENABLED
        self._prev = _ENABLED
        _ENABLED = self._target
        return self

    def __exit__(self, *exc: object) -> None:
        global _ENABLED
        _ENABLED = self._prev


def force_enabled() -> _EnabledScope:
    return _EnabledScope(True)


def force_disabled() -> _EnabledScope:
    return _EnabledScope(False)
