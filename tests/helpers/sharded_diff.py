"""Four-way differential worker for the sweep engines (subprocess side).

Runs one named scenario set through the ``fused`` / ``sharded`` /
``batched`` / ``scalar`` engines in a fresh interpreter (so the parent
test can pin the virtual-device count via ``XLA_FLAGS``) and asserts:

* ``fused`` vs ``batched``: step-for-step :meth:`ScenarioResult.allclose`
  at 1e-9 plus summary agreement at 1e-12 relative. Not bit-for-bit: the
  XLA:CPU backend contracts multiply-adds into FMAs, which perturbs the
  last ulp (see docs/SCALING.md); observed agreement is ~1e-15 relative.
  The fused engine runs at every device count, *including 1* (interval
  fusion does not require a mesh).
* ``sharded`` vs ``batched``: the same bound (engine skipped when the
  worker runs with a single device — ``sharded`` requires a mesh).
* ``batched`` vs ``scalar``: bit-for-bit identical JSON digests (the
  pre-existing invariant — neither device engine may disturb it).
* the compiled sharded step **and** the compiled fused interval scan
  contain **no cross-scenario collectives**.

Invoked by ``tests/test_sweep_sharded.py`` / ``tests/test_sweep_golden.py``
through the ``run_under_devices`` fixture::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python tests/helpers/sharded_diff.py \
        --devices 4 --case ragged

``--case reject`` asserts the single-device guard instead (run it with one
visible device). ``--case golden --regen`` rewrites
``tests/golden/sweep_small.json`` from the scalar oracle.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent.parent
GOLDEN_PATH = REPO / "tests" / "golden" / "sweep_small.json"

#: volatile SweepResult keys (timers + the engine label itself)
VOLATILE = ("engine", "wall_s", "model_update_wall_s",
            "forecast_update_wall_s", "model_update_compile_wall_s",
            "forecast_update_compile_wall_s")

#: substrings whose presence in the compiled step would mean the scenario
#: axis stopped partitioning cleanly
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


def _specs(case: str):
    from repro.dsp import (FailuresAt, NoFailures, PeriodicFailures,
                           ScenarioSpec, make_trace, scenario_grid)
    if case in ("uniform", "golden"):
        traces = [make_trace(k, duration_s=900.0, dt_s=5.0)
                  for k in ("diurnal", "flash")]
        return scenario_grid(traces, ("static", "reactive"), (0,),
                             failures=PeriodicFailures(420.0))
    if case == "ragged":
        # 5 scenarios: never divisible by 2 or 4 devices -> padding rows;
        # mixed durations + overlapping failure schedules on top.
        return [
            ScenarioSpec(trace=make_trace("diurnal", duration_s=600.0,
                                          dt_s=5.0),
                         controller="reactive", seed=3,
                         failures=FailuresAt(100.0, 150.0, 400.0)),
            ScenarioSpec(trace=make_trace("flash", duration_s=900.0,
                                          dt_s=5.0),
                         controller="static", seed=1,
                         failures=PeriodicFailures(300.0)),
            ScenarioSpec(trace=make_trace("regime", duration_s=900.0,
                                          dt_s=5.0),
                         controller="ds2", seed=2),
            ScenarioSpec(trace=make_trace("sindrift", duration_s=750.0,
                                          dt_s=5.0),
                         controller="reactive", seed=0,
                         failures=PeriodicFailures(350.0) | FailuresAt(80.0)),
            ScenarioSpec(trace=make_trace("diurnal", duration_s=450.0,
                                          dt_s=5.0),
                         controller="static", seed=4),
        ]
    if case == "demeter":
        return [
            ScenarioSpec(trace=make_trace("diurnal", duration_s=1800.0,
                                          dt_s=5.0),
                         controller="demeter", seed=0,
                         failures=NoFailures()),
            ScenarioSpec(trace=make_trace("flash", duration_s=1800.0,
                                          dt_s=5.0),
                         controller="demeter", seed=1,
                         failures=NoFailures(), forecaster="holt"),
            ScenarioSpec(trace=make_trace("regime", duration_s=1800.0,
                                          dt_s=5.0),
                         controller="reactive", seed=2,
                         failures=PeriodicFailures(600.0)),
        ]
    raise SystemExit(f"unknown case {case!r}")


def _approx(a, b, rel: float, path: str = "$") -> float:
    """Recursive JSON comparison, floats at ``|a - b| <= rel * (1 + |b|)``
    (``np.isclose`` with ``rtol = atol = rel``), everything else exactly.
    Any mismatch raises; otherwise returns the largest float error
    ``|a - b| / (1 + |b|)`` found, so a caller can report its margin."""
    if isinstance(a, float) and isinstance(b, float):
        if a == b or (np.isnan(a) and np.isnan(b)):
            return 0.0
        err = abs(a - b) / (1.0 + abs(b))
        assert err <= rel, f"{path}: {a!r} != {b!r}"
        return err
    assert type(a) is type(b), f"{path}: {type(a)} != {type(b)}"
    if isinstance(a, dict):
        assert a.keys() == b.keys(), f"{path}: keys {a.keys()} != {b.keys()}"
        return max((_approx(a[k], b[k], rel, f"{path}.{k}") for k in a),
                   default=0.0)
    if isinstance(a, list):
        assert len(a) == len(b), f"{path}: len {len(a)} != {len(b)}"
        return max((_approx(x, y, rel, f"{path}[{i}]")
                    for i, (x, y) in enumerate(zip(a, b))), default=0.0)
    assert a == b, f"{path}: {a!r} != {b!r}"
    return 0.0


def _strip(js: dict) -> dict:
    return {k: v for k, v in js.items() if k not in VOLATILE}


def check_reject() -> None:
    import jax
    assert jax.device_count() == 1, "reject case expects one device"
    from repro.core import EngineConfig
    try:
        EngineConfig(sim_backend="sharded")
    except ValueError as e:
        msg = str(e)
        assert "at least 2 devices" in msg, msg
        assert "xla_force_host_platform_device_count" in msg, \
            f"error is not actionable: {msg}"
    else:
        raise AssertionError("sharded accepted with one visible device")
    # ... while the fused engine needs no mesh: one device is fine
    assert EngineConfig(sim_backend="fused").sim_backend == "fused"
    # ... and the remedy actually names a working spelling
    print("REJECT-OK")


def run_case(case: str, devices: int) -> None:
    import jax
    assert jax.device_count() == devices, \
        f"expected {devices} devices, backend has {jax.device_count()}"
    from repro.core import EngineConfig
    from repro.dsp import run_sweep
    from repro.dsp.sweep import SweepEngine

    specs = _specs(case)
    batched = run_sweep(specs)
    scalar = run_sweep(specs, config=EngineConfig(sim_backend="scalar"))
    for b, c in zip(batched.scenarios, scalar.scenarios):
        assert b.name == c.name
        assert b.allclose(c), f"{b.name}: batched != scalar"
    assert _strip(batched.to_json()) == _strip(scalar.to_json())

    # observability must never perturb results: an obs-enabled run yields
    # the bit-identical digest (timers stripped), with spans recorded
    from repro import obs
    obs.enable(clear=True)
    try:
        obs_run = run_sweep(specs)
    finally:
        obs.disable()
    assert _strip(obs_run.to_json()) == _strip(batched.to_json()), \
        "obs instrumentation perturbed sweep results"
    assert obs.tracer().events, "obs-enabled run recorded no spans"

    # fused engine: runs at every device count, including 1
    feng = SweepEngine(specs, config=EngineConfig(sim_backend="fused",
                                                  devices=devices))
    fused = feng.run()
    assert fused.engine == "fused"
    fex = feng.executor
    assert fex.n_devices == devices
    assert fex.n_rows % devices == 0 and fex.n_rows >= len(specs)

    # no cross-scenario collectives in the compiled interval scan
    compiled = fex.lower_interval().compile().as_text()
    present = [c for c in COLLECTIVES if c in compiled]
    assert not present, f"collectives in fused interval scan: {present}"

    for a, b in zip(fused.scenarios, batched.scenarios):
        assert a.name == b.name
        assert a.allclose(b), f"{a.name}: fused != batched"
    _approx(_strip(fused.to_json()), _strip(batched.to_json()), 1e-12)

    engines, sharded = ["fused", "batched", "scalar"], None
    if devices >= 2:            # sharded requires a mesh
        eng = SweepEngine(specs, config=EngineConfig(sim_backend="sharded",
                                                     devices=devices))
        sharded = eng.run()
        assert sharded.engine == "sharded"
        ex = eng.executor
        assert ex.n_devices == devices
        assert ex.n_rows % devices == 0 and ex.n_rows >= len(specs)

        # no cross-scenario collectives in the compiled step
        compiled = ex.lower_step().compile().as_text()
        present = [c for c in COLLECTIVES if c in compiled]
        assert not present, f"collectives in sharded step: {present}"

        for a, b in zip(sharded.scenarios, batched.scenarios):
            assert a.name == b.name
            assert a.allclose(b), f"{a.name}: sharded != batched"
        _approx(_strip(sharded.to_json()), _strip(batched.to_json()), 1e-12)
        engines.insert(0, "sharded")

    if case == "golden":
        golden = json.loads(GOLDEN_PATH.read_text())
        assert _strip(scalar.to_json()) == golden, \
            "scalar oracle drifted from tests/golden/sweep_small.json"
        assert _strip(batched.to_json()) == golden, \
            "batched engine drifted from tests/golden/sweep_small.json"
        _approx(_strip(fused.to_json()), golden, 1e-12)
        if sharded is not None:
            _approx(_strip(sharded.to_json()), golden, 1e-12)
    if case == "demeter":
        assert fused.n_model_fits == batched.n_model_fits
        assert fused.n_forecast_updates == batched.n_forecast_updates > 0
        if sharded is not None:
            assert sharded.n_model_fits == batched.n_model_fits
            assert sharded.n_forecast_updates == batched.n_forecast_updates
    print(f"DIFF-OK case={case} devices={devices} "
          f"scenarios={len(specs)} rows={fex.n_rows} "
          f"engines={'/'.join(engines)}")


def make_golden() -> None:
    from repro.core import EngineConfig
    from repro.dsp import run_sweep
    res = run_sweep(_specs("golden"),
                    config=EngineConfig(sim_backend="scalar"))
    GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
    GOLDEN_PATH.write_text(json.dumps(_strip(res.to_json()), indent=2,
                                      sort_keys=True) + "\n")
    print(f"wrote {GOLDEN_PATH}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--case", required=True,
                    choices=("uniform", "ragged", "demeter", "golden",
                             "reject"))
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--regen", action="store_true",
                    help="rewrite the golden file (case=golden only)")
    args = ap.parse_args()
    if args.case == "reject":
        check_reject()
    elif args.case == "golden" and args.regen:
        make_golden()
    else:
        run_case(args.case, args.devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
