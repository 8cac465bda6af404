"""Paper-table benchmarks + the multi-scenario sweep CLI.

Two entry points:

* ``python benchmarks/dsp_experiments.py paper`` — the paper's (trace x
  method) cells (Fig. 5/6, Table 3) through the scalar protocol harness,
  cached as pickles under ``results/dsp_runs``.
* ``python benchmarks/dsp_experiments.py sweep`` — a ScenarioSpec grid
  (trace class x controller x seed) through the batched sweep engine, with
  per-scenario JSON results and an optional batched-vs-scalar verification +
  wall-clock speedup report (``--compare-scalar``).
"""
from __future__ import annotations

import argparse
import json
import os
import pickle
import time
from dataclasses import replace
from typing import Dict, List

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import FORECASTER_KINDS, EngineConfig
from repro.dsp import (PeriodicFailures, RunResult, run_experiment, run_sweep,
                       scenario_grid, make_trace, tsw_like, ysb_like,
                       TRACE_GENERATORS)

METHODS = ("static", "demeter", "reactive", "ds2")
CACHE_DIR = "results/dsp_runs"
SWEEP_DIR = "results/sweeps"


def get_runs(duration_h: float = 3.0, dt_s: float = 10.0, seed: int = 0,
             traces: tuple = ("ysb", "tsw")) -> Dict[str, Dict[str, RunResult]]:
    os.makedirs(CACHE_DIR, exist_ok=True)
    out: Dict[str, Dict[str, RunResult]] = {}
    for tname in traces:
        trace = (ysb_like if tname == "ysb" else tsw_like)(
            duration_s=duration_h * 3600.0, dt_s=dt_s)
        out[tname] = {}
        for method in METHODS:
            key = f"{tname}_{method}_{duration_h:g}h_dt{dt_s:g}_s{seed}"
            path = os.path.join(CACHE_DIR, key + ".pkl")
            if os.path.exists(path):
                with open(path, "rb") as f:
                    out[tname][method] = pickle.load(f)
                continue
            t0 = time.time()
            res = run_experiment(trace, method, seed=seed)
            with open(path, "wb") as f:
                pickle.dump(res, f)
            print(f"# ran {key} in {time.time()-t0:.0f}s", flush=True)
            out[tname][method] = res
    return out


# -- Table 3: recovery times & reconfigurations ------------------------------
def table3(runs: Dict[str, Dict[str, RunResult]]) -> List[str]:
    lines = []
    for tname, by_method in runs.items():
        for method, res in by_method.items():
            rec = []
            for f in res.failures:
                if f.recovery_s is None:
                    rec.append("NR")
                elif not np.isfinite(f.recovery_s):
                    rec.append("6m+")
                else:
                    rec.append(f"{f.recovery_s:.0f}s")
            lines.append(f"{tname},{method},delta={res.n_reconfigurations},"
                         f"recoveries={'|'.join(rec)}")
    return lines


def recovery_deviation_vs_static(runs) -> Dict[str, Dict[str, float]]:
    out = {}
    for tname, by_method in runs.items():
        stat = [r for r in by_method["static"].recovery_times()
                if r is not None and np.isfinite(r)]
        base = np.mean(stat) if stat else np.nan
        out[tname] = {}
        for method, res in by_method.items():
            ok = [r for r in res.recovery_times()
                  if r is not None and np.isfinite(r)]
            out[tname][method] = (np.mean(ok) / base - 1.0) * 100.0 \
                if ok and base else float("nan")
    return out


# -- Fig 6a/b: latency ECDF ---------------------------------------------------
def latency_optimal_fraction(runs, band_s: float = 2.0
                             ) -> Dict[str, Dict[str, float]]:
    return {t: {m: res.frac_latency_below(band_s)
                for m, res in by.items()} for t, by in runs.items()}


# -- Fig 6c/d: cumulative resource usage ----------------------------------------
def resource_usage_vs_static(runs) -> Dict[str, Dict[str, Dict[str, float]]]:
    out = {}
    for tname, by in runs.items():
        cpu0 = by["static"].cumulative_cpu_s()
        mem0 = by["static"].cumulative_mem_mb_s()
        out[tname] = {}
        for m, res in by.items():
            out[tname][m] = {
                "cpu_net": res.cumulative_cpu_s(True) / cpu0,
                "cpu_gross": res.cumulative_cpu_s(False) / cpu0,
                "mem_net": res.cumulative_mem_mb_s(True) / mem0,
                "mem_gross": res.cumulative_mem_mb_s(False) / mem0,
            }
    return out


# -- Fig 6e/f: usage trend over time -------------------------------------------
def usage_trend(runs) -> Dict[str, Dict[str, float]]:
    """Regression slope of Demeter's CPU usage over time (per hour,
    normalized by the mean) — the paper's 'savings keep growing' claim."""
    out = {}
    for tname, by in runs.items():
        res = by["demeter"]
        t = res.times / 3600.0
        u = res.usage_cpu
        mask = np.isfinite(u)
        slope = np.polyfit(t[mask], u[mask], 1)[0]
        out[tname] = {"cpu_slope_per_h": float(slope / max(u.mean(), 1e-9))}
    return out


# -- sweep CLI ----------------------------------------------------------------
def _csv(value: str) -> List[str]:
    return [v.strip() for v in value.split(",") if v.strip()]


def sweep_main(args: argparse.Namespace) -> None:
    duration_s = args.duration_h * 3600.0
    traces = [make_trace(k, duration_s=duration_s, dt_s=args.dt)
              for k in args.traces]
    failures = PeriodicFailures(args.failure_interval_m * 60.0)
    specs = scenario_grid(traces, args.controllers, args.seeds,
                          failures=failures)
    if args.forecasters != ["arima"]:
        # per-scenario forecaster choice: cycle the requested kinds
        specs = [replace(s, forecaster=args.forecasters[i %
                                                        len(args.forecasters)])
                 for i, s in enumerate(specs)]
    print(f"# sweep: {len(specs)} scenarios "
          f"({len(traces)} traces x {len(args.controllers)} controllers "
          f"x {len(args.seeds)} seeds), {args.duration_h:g}h @ dt={args.dt:g}s")

    config = EngineConfig(sim_backend=args.engine, devices=args.devices,
                          fit_backend=args.fit_backend,
                          forecast_backend=args.forecast_backend)
    from repro import obs
    if args.trace_out:
        obs.enable(clear=True)
    try:
        batched = run_sweep(specs, config=config)
    finally:
        if args.trace_out:
            obs.disable()
    if args.trace_out:
        os.makedirs(os.path.dirname(args.trace_out) or ".", exist_ok=True)
        obs.write_chrome_trace(args.trace_out)
        print(f"# wrote Chrome trace (load in https://ui.perfetto.dev) "
              f"to {args.trace_out}")
    print(f"# {batched.engine} engine: {batched.wall_s:.2f}s wall "
          f"({batched.n_steps} steps x {len(specs)} scenarios)")
    if batched.n_model_fits:
        print(f"# model updates ({args.fit_backend}): "
              f"{batched.n_model_fits} GP fits, "
              f"{batched.model_update_wall_s:.2f}s wall")
    if batched.n_forecast_updates:
        print(f"# forecast updates ({args.forecast_backend}): "
              f"{batched.n_forecast_updates} stream-updates, "
              f"{batched.forecast_update_wall_s:.3f}s TSF wall")

    if args.compare_scalar:
        scalar = run_sweep(specs, config=config.replace(sim_backend="scalar"))
        mismatched = [a.name for a, b in
                      zip(batched.scenarios, scalar.scenarios)
                      if not a.allclose(b)]
        print(f"# scalar reference: {scalar.wall_s:.2f}s wall -> "
              f"speedup {scalar.wall_s / max(batched.wall_s, 1e-9):.2f}x")
        print(f"# {batched.engine}-vs-scalar equivalence: "
              f"{'OK' if not mismatched else 'MISMATCH ' + str(mismatched)}")

    os.makedirs(args.out, exist_ok=True)
    for sc in batched.scenarios:
        path = os.path.join(args.out,
                            sc.name.replace("/", "_") + ".json")
        with open(path, "w") as f:
            json.dump(sc.summary(), f, indent=2)
    # sweep.json goes through the exporter schema: engine/devices/seed
    # live in the leg payload (never the filename), walls + compile split
    # ride along as the bench section's metrics.
    devices = args.devices
    if devices is None:
        if args.engine in ("sharded", "fused"):
            import jax
            devices = jax.device_count()
        else:
            devices = 1
    sweep_metrics = {k: v for k, v in batched.to_json().items()
                     if k != "scenarios"}
    leg = obs.make_leg(
        engine=batched.engine, devices=devices, seed=args.seeds[0],
        mode="sweep", scenarios=len(specs), n_steps=batched.n_steps,
        wall_s=batched.wall_s,
        scenario_steps_per_s=(len(specs) * batched.n_steps
                              / max(batched.wall_s, 1e-12)))
    sweep_params = {"traces": args.traces, "controllers": args.controllers,
                    "seeds": args.seeds, "duration_h": args.duration_h,
                    "dt": args.dt,
                    "failure_interval_m": args.failure_interval_m,
                    "forecasters": args.forecasters}
    obs.merge_bench(os.path.join(args.out, "sweep.json"), "dsp_sweep",
                    [leg], params=sweep_params, metrics=sweep_metrics)
    if args.bench:
        obs.merge_bench(args.bench, "dsp_sweep", [leg],
                        params=sweep_params, metrics=sweep_metrics)
        print(f"# merged dsp_sweep leg into {args.bench}")
    print(f"# wrote {len(batched.scenarios)} scenario JSONs to {args.out}")

    hdr = f"{'scenario':32s} {'p50':>7s} {'p95':>7s} {'<2s':>6s} " \
          f"{'cpu(core-s)':>12s} {'reconf':>6s} {'fails':>5s}"
    print(hdr)
    for sc in batched.scenarios:
        s = sc.summary()
        print(f"{s['name']:32s} {s['latency_p50_s']:7.2f} "
              f"{s['latency_p95_s']:7.2f} {s['frac_latency_below_2s']:6.1%} "
              f"{s['cumulative_cpu_core_s']:12.0f} "
              f"{s['n_reconfigurations']:6d} {s['n_failures_injected']:5d}")


def paper_main(args: argparse.Namespace) -> None:
    runs = get_runs(duration_h=args.duration_h, dt_s=args.dt)
    for line in table3(runs):
        print(line)
    print("latency<2s:", latency_optimal_fraction(runs))
    print("usage vs static:", resource_usage_vs_static(runs))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    sw = sub.add_parser("sweep", help="batched multi-scenario sweep")
    sw.add_argument("--traces", type=_csv,
                    default=["diurnal", "flash", "regime"],
                    help=f"trace classes ({','.join(sorted(TRACE_GENERATORS))})")
    sw.add_argument("--controllers", type=_csv,
                    default=["static", "reactive", "ds2"])
    sw.add_argument("--seeds", type=lambda v: [int(x) for x in _csv(v)],
                    default=[0, 1])
    sw.add_argument("--duration-h", type=float, default=2.0)
    sw.add_argument("--dt", type=float, default=5.0)
    sw.add_argument("--failure-interval-m", type=float, default=45.0)
    sw.add_argument("--out", default=SWEEP_DIR)
    sw.add_argument("--trace-out", default=None,
                    help="enable obs instrumentation for the sweep and "
                         "write a Chrome-trace JSON here (loadable in "
                         "Perfetto / chrome://tracing)")
    sw.add_argument("--bench", default=None,
                    help="also merge the sweep leg into this bench "
                         "trajectory file (e.g. BENCH_sweep.json)")
    sw.add_argument("--compare-scalar", action="store_true",
                    help="also run the scalar reference oracle; verify "
                         "equivalence and report the wall-clock speedup")
    sw.add_argument("--engine",
                    choices=("batched", "scalar", "sharded", "fused"),
                    default="batched",
                    help="simulation engine: single-device vectorized "
                         "(default), per-scenario reference oracle, "
                         "device-sharded (needs >= 2 visible devices; on "
                         "CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N — see docs/SCALING.md), or fused "
                         "(whole decision intervals in one on-device scan)")
    sw.add_argument("--devices", type=int, default=None,
                    help="scenario-mesh width for --engine sharded/fused "
                         "and the shared GP/forecast banks (default: all "
                         "visible devices)")
    sw.add_argument("--fit-backend", choices=("bank", "scalar"),
                    default="bank",
                    help="Demeter GP fitting path: batched jitted GPBank "
                         "(default) or the per-GP scipy reference oracle")
    sw.add_argument("--forecast-backend", choices=("bank", "scalar"),
                    default="bank",
                    help="Demeter TSF path: shared batched ForecastBank "
                         "(default) or per-scenario NumPy reference oracle")
    sw.add_argument("--forecasters", type=_csv, default=["arima"],
                    help=f"forecaster kinds ({','.join(FORECASTER_KINDS)}), "
                         "cycled across scenarios")
    sw.set_defaults(func=sweep_main)

    pp = sub.add_parser("paper", help="paper-protocol cells (Table 3 etc.)")
    pp.add_argument("--duration-h", type=float, default=3.0)
    pp.add_argument("--dt", type=float, default=10.0)
    pp.set_defaults(func=paper_main)

    args = ap.parse_args()
    enable_compile_cache()
    args.func(args)


if __name__ == "__main__":
    main()
