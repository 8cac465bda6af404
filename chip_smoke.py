"""Drive the system's main path once on a TPU and check what comes out.

Run from the root of a checkout::

    python3 chip_smoke.py              # one chip: fleet + fused sweep + golden
    python3 chip_smoke.py --chips 4    # four chips: the scenario-mesh path only

One chip, three phases, all through the public entry points:

* **fleet** — ``repro.fleet.loadgen.run_soak`` with 1024 jobs for 8 epochs:
  the epoch ingest drain, the shared ForecastBank/DetectorBank flushes and
  ``ModelBank.batch_refresh`` run on the device. Fails unless decisions,
  warm controllers and accepted ingest samples are all non-zero.
* **sweep** — the fused engine over traces diurnal/flash/regime/sindrift x
  controllers static/reactive/ds2/demeter x seeds 0-3 (64 scenarios, 2 h
  at dt = 5 s, the paper's 45-minute periodic failures, Demeter profiling
  every 600 s so that it fits GPs). Fails if no GP was fitted, and unless
  every scenario agrees with the host ``"batched"`` numpy engine on the
  same grid within :data:`SWEEP_RTOL`.
* **golden** — the small golden grid through the fused engine, against
  ``tests/golden/sweep_small.json`` (the scalar oracle's digest).

``--chips 4`` runs only what exists across chips: the same 64-scenario grid
through the ``fused`` and ``sharded`` engines at ``devices=4`` (scenario
mesh, bank padding), each compared with the host ``"batched"`` engine, and
every persistent device buffer must span all four devices.

The device guard comes first: on a machine where JAX finds no TPU the
script exits non-zero naming the platform it found, and never runs a phase
on the CPU. A failing phase raises, so the script exits non-zero; nothing
is caught and continued. Only a run in which every phase passed prints its
last stdout line, one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One process holds the chip for the whole run; it starts no child process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

REPO = Path(__file__).resolve().parent

#: Fused-vs-batched agreement bound on the chip (relative error per
#: element, floored at |b| = RTOL_FLOOR). XLA:TPU emulates float64, so
#: the 1e-12 of the CPU differential does not carry over: on a v5e the
#: emulated simulator drifts from numpy by up to ~1e-11 over the 1440-tick
#: smoke grid (3 of 64 scenarios exceed 1e-12), hence 1e-10.
SWEEP_RTOL = 1e-10
#: |b| below which an element's error is taken relative to this floor
RTOL_FLOOR = 1e-6
#: Golden-digest agreement bound on the chip, the CPU golden test's own
#: (``tests/helpers/sharded_diff._approx``: ``|a - b| <= rtol * (1 + |b|)``).
GOLDEN_RTOL = 1e-12

SWEEP_TRACES = ("diurnal", "flash", "regime", "sindrift")
SWEEP_CONTROLLERS = ("static", "reactive", "ds2", "demeter")
SWEEP_SEEDS = (0, 1, 2, 3)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or empty result."""


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# device guard and set-up
# ---------------------------------------------------------------------------

def require_tpu(chips: int = 1) -> list:
    """The visible TPU devices; exits non-zero on any other platform."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform {platform!r} "
            f"({devs[0].device_kind}, {len(devs)} device(s)); this script "
            f"does not run on the CPU")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX found {len(devs)}")
    return devs


def import_repo() -> None:
    """Put the checkout's ``src/`` and ``tests/`` on the path and check
    that the package is there (it is not when this file stands alone)."""
    for sub in ("src", "tests"):
        p = str(REPO / sub)
        if p not in sys.path:
            sys.path.insert(0, p)
    import repro  # noqa: F401  (ModuleNotFoundError outside a checkout)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling since creation
    (read from ``jax.monitoring``): the set-up share of a phase's wall."""

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


def timed(fn, *args, **kwargs) -> Tuple[object, float, float, int]:
    """``(result, wall_s, compile_s, n_compiles)`` of one call."""
    clock = CompileClock()
    try:
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
    finally:
        clock.close()
    return out, wall, clock.seconds, clock.compiles


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

_ARRAYS = ("times", "rates", "latencies", "usage_cpu", "usage_mem_mb",
           "workers", "consumer_lag")


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    """max |a - b| / max(|b|, RTOL_FLOOR); NaN where only one side is NaN
    counts as infinite."""
    a = np.asarray(a, float)
    b = np.asarray(b, float)
    if a.shape != b.shape:
        return float("inf")
    both_nan = np.isnan(a) & np.isnan(b)
    d = np.abs(a - b) / np.maximum(np.abs(b), RTOL_FLOOR)
    d = np.where(both_nan, 0.0, d)
    d = np.where(np.isnan(d), np.inf, d)
    return float(d.max()) if d.size else 0.0


def compare_sweeps(got, ref, rtol: float) -> Tuple[float, List[str]]:
    """Max per-element relative error over every scenario, and the names
    of scenarios that disagree (``ScenarioResult.allclose`` at ``rtol``,
    which also pins reconfiguration counts and recovery bookkeeping).
    A different number of GP fits is a disagreement of the whole grid."""
    if len(got.scenarios) != len(ref.scenarios):
        raise SmokeFailure(f"{len(got.scenarios)} scenarios vs "
                           f"{len(ref.scenarios)}")
    worst = 0.0
    bad = []
    for a, b in zip(got.scenarios, ref.scenarios):
        if a.name != b.name:
            raise SmokeFailure(f"scenario order differs: {a.name} vs {b.name}")
        worst = max(worst, *(rel_err(getattr(a, k), getattr(b, k))
                             for k in _ARRAYS))
        if not a.allclose(b, rtol=rtol, atol=rtol * RTOL_FLOOR):
            bad.append(a.name)
    if got.n_model_fits != ref.n_model_fits:
        bad.append(f"n_model_fits {got.n_model_fits} vs {ref.n_model_fits}")
    return worst, bad


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_fleet(n_jobs: int = 1024, epochs: int = 8) -> Dict:
    """The fleet service loop over ``n_jobs`` synthetic jobs."""
    from repro.fleet.loadgen import SoakConfig, run_soak
    res, wall, compile_s, n_compiles = timed(
        run_soak, SoakConfig(n_jobs=n_jobs, epochs=epochs))
    stats = res["stats"]
    out = {"jobs": n_jobs, "epochs": epochs,
           "decisions": res["decisions"], "warm": stats["warm"],
           "ingest_accepted": stats["ingest"]["accepted"],
           "gp_fits": stats["model_fits"],
           "decision_digest": res["decision_digest"],
           "wall_s": wall, "setup_compile_s": compile_s,
           "compiles": n_compiles}
    log("fleet", json.dumps(out))
    for key in ("decisions", "warm", "ingest_accepted"):
        if not out[key]:
            raise SmokeFailure(f"fleet: {key} is 0")
    return out


def sweep_specs(traces: Sequence[str] = SWEEP_TRACES,
                controllers: Sequence[str] = SWEEP_CONTROLLERS,
                seeds: Sequence[int] = SWEEP_SEEDS,
                hours: float = 2.0, dt_s: float = 5.0):
    """The smoke grid: traces x controllers x seeds under the paper's
    periodic failures."""
    from repro.dsp import PeriodicFailures, make_trace, scenario_grid
    from repro.dsp.runner import FAILURE_INTERVAL_S
    trs = [make_trace(k, duration_s=hours * 3600.0, dt_s=dt_s)
           for k in traces]
    return scenario_grid(trs, controllers, seeds,
                         failures=PeriodicFailures(FAILURE_INTERVAL_S))


def _config(sim_backend: str, devices: Optional[int] = None):
    from repro.core import DemeterHyperParams, EngineConfig
    # profile_interval_s=600: Demeter's first profiling round lands inside
    # a 2 h run, so the sweep fits GPs (paper default 1500 s does not).
    return EngineConfig(sim_backend=sim_backend, devices=devices,
                        hp=DemeterHyperParams(profile_interval_s=600.0))


def run_engine(specs, sim_backend: str, devices: Optional[int] = None):
    """``(SweepEngine, SweepResult, wall_s, compile_s)`` of one sweep."""
    from repro.dsp.sweep import SweepEngine
    eng = SweepEngine(specs, config=_config(sim_backend, devices))
    res, wall, compile_s, _ = timed(eng.run)
    return eng, res, wall, compile_s


def _sweep_line(res, wall: float, compile_s: float) -> Dict:
    S = len(res.scenarios)
    return {"engine": res.engine, "scenarios": S, "steps": res.n_steps,
            "n_model_fits": res.n_model_fits,
            "n_forecast_updates": res.n_forecast_updates,
            "wall_s": wall, "setup_compile_s": compile_s,
            "scenario_steps_per_s": S * res.n_steps / max(wall, 1e-9)}


def phase_sweep(specs, rtol: float = SWEEP_RTOL) -> Dict:
    """The fused engine over ``specs``, checked against the host engine."""
    eng, fused, wall, comp = run_engine(specs, "fused")
    line = _sweep_line(fused, wall, comp)
    line["tick"] = eng.executor.tick
    log("sweep", json.dumps(line))
    if fused.n_model_fits == 0:
        raise SmokeFailure("sweep: Demeter fitted no GP model")
    _, batched, bwall, bcomp = run_engine(specs, "batched")
    log("sweep", json.dumps(_sweep_line(batched, bwall, bcomp)))
    worst, bad = compare_sweeps(fused, batched, rtol)
    out = {"fused": line, "max_rel_err_vs_batched": worst, "rtol": rtol,
           "disagreeing": bad}
    log("sweep", f"fused vs batched: max relative error {worst!r} "
                 f"(bound {rtol!r}), {len(bad)} scenario(s) outside")
    if bad:
        raise SmokeFailure(f"sweep: fused disagrees with batched on "
                           f"{bad[:8]}")
    return out


def phase_golden(rtol: float = GOLDEN_RTOL) -> Dict:
    """The golden grid through the fused engine vs the checked-in digest."""
    from helpers.sharded_diff import GOLDEN_PATH, _approx, _specs, _strip
    _, res, wall, comp = run_engine(_specs("golden"), "fused")
    # raises on any float beyond rtol or any other difference
    err = _approx(_strip(res.to_json()), json.loads(GOLDEN_PATH.read_text()),
                  rtol)
    out = {"max_rel_err": err, "rtol": rtol, "wall_s": wall,
           "setup_compile_s": comp}
    log("golden", json.dumps(out))
    return out


def persistent_buffers(eng) -> Dict[str, object]:
    """Every device buffer a sweep keeps across dispatches, as the engine
    and the shared forecast bank report them."""
    bufs = {f"executor.{k}": v
            for k, v in eng.executor.device_buffers().items()}
    if eng.forecast_bank is not None:
        bufs.update({f"forecast_bank.{k}": v
                     for k, v in eng.forecast_bank.device_buffers().items()})
    return bufs


def phase_mesh(specs, devices: int = 4, rtol: float = SWEEP_RTOL) -> Dict:
    """fused and sharded at ``devices`` wide vs the host engine."""
    _, batched, bwall, bcomp = run_engine(specs, "batched")
    log("mesh", json.dumps(_sweep_line(batched, bwall, bcomp)))
    out: Dict = {}
    for engine in ("fused", "sharded"):
        eng, res, wall, comp = run_engine(specs, engine, devices)
        line = _sweep_line(res, wall, comp)
        if engine == "fused":
            line["tick"] = eng.executor.tick
        spans = {name: len(arr.sharding.device_set)
                 for name, arr in persistent_buffers(eng).items()}
        line["buffer_device_counts"] = spans
        log("mesh", json.dumps(line))
        narrow = {k: v for k, v in spans.items() if v != devices}
        if narrow:
            raise SmokeFailure(f"{engine}: buffers not spread over "
                               f"{devices} devices: {narrow}")
        worst, bad = compare_sweeps(res, batched, rtol)
        log("mesh", f"{engine} vs batched: max relative error {worst!r} "
                    f"(bound {rtol!r}), {len(bad)} scenario(s) outside")
        if bad:
            raise SmokeFailure(f"{engine} disagrees with batched on "
                               f"{bad[:8]}")
        out[engine] = {**line, "max_rel_err_vs_batched": worst}
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fleet + fused sweep + golden; 4: the "
                         "scenario-mesh sweeps only")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    import_repo()
    from repro.compile_cache import enable_compile_cache
    log("setup", f"{len(devs)} x {devs[0].device_kind}; compile cache "
                 f"{enable_compile_cache()}")

    report: Dict = {}
    if args.chips == 1:
        report["fleet"] = phase_fleet()
        report["sweep"] = phase_sweep(sweep_specs())
        report["golden"] = phase_golden()
    else:
        report["mesh"] = phase_mesh(sweep_specs(), devices=args.chips)

    out_dir = REPO / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"chip_smoke_{args.chips}.json").write_text(
        json.dumps(report, indent=1, default=str) + "\n")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
