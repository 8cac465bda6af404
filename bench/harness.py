"""What every cell shares: the cell's files, the device guard, compile
accounting and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file (``configs[].file``), its traffic mix
(``bench/mixes/<traffic>.json``), the driver of the mix's kind
(``bench/kinds/<kind>.py``), the rate shape its configuration names
(``bench/shapes/<shape>.py``) and its per-layer readers
(``bench/metrics/<metric>.py``). Adding a cell, a kind of traffic, a shape
or a metric adds files; it edits none.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"


class CellError(RuntimeError):
    """The cell cannot be run as described (bad name, missing file)."""


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The workload entry with its configuration and mix resolved:
    ``{"workload", "config", "mix", "end_to_end", "per_layer"}``. Metrics
    that list ``workloads`` are kept only where they name this cell."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / cfgs[w["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{w['traffic']}.json").read_text())

    def mine(ms: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        return [m for m in ms if name in m.get("workloads", [name])]

    return {"workload": w, "config": config, "mix": mix,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def load_named(folder: str, name: str) -> ModuleType:
    """``bench/<folder>/<name>.py`` as a module (loaded once per process).
    File names may hold dots, so the module is loaded from its path."""
    key = f"bench.{folder}.{name.replace('.', '_')}"
    if key in sys.modules:
        return sys.modules[key]
    path = BENCH / folder / f"{name}.py"
    if not path.exists():
        raise CellError(f"no {folder[:-1]} named {name!r} "
                        f"({path.relative_to(ROOT)})")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def load_kind(cell: Dict[str, Any]) -> ModuleType:
    """The driver of the cell's traffic kind: ``bench/kinds/<kind>.py``,
    with ``run(cell, seed, seconds, trace, t_start, devs)``."""
    return load_named("kinds", cell["mix"]["kind"])


def load_reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    return load_named("metrics", metric).read


# ---------------------------------------------------------------------------
# device guard and set-up
# ---------------------------------------------------------------------------

def require_tpu(chips: int) -> list:
    """The TPU devices; exits non-zero, before any result, on any other
    platform or with fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found platform "
                         f"{devs[0].platform!r} ({len(devs)} device(s)); "
                         f"no run on another platform")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU chip(s), JAX "
                         f"found {len(devs)}")
    return devs


def import_program() -> None:
    """Put the checkout's ``src/`` on the path; fails where the program
    is absent (a directory holding only the benchmark)."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``.jax_cache/`` in the checkout), caching every program, however
    small, so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number of
    backend compiles, while open (from ``jax.monitoring``)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += secs
            if event == self.EVENTS[-1]:
                self.compiles += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)


class GcClock:
    """Collections of Python's oldest generation, and their seconds, while
    open: the pauses a long-lived heap can put into a window."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


def device_info(devs: list) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them, with the peak bytes in
    use on the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def print_result(result: Dict[str, Any]) -> None:
    """The checks, last on standard error, then the result as the last line
    of standard output (``checks`` is its last key)."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.perf_counter()
