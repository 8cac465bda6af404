"""The ``sweep`` kind: scenario grids through ``SweepEngine.run`` on the
engine the mix names.

The mix names the controllers, how many scenarios each gets and the
engine; every scenario replays its own rate trace (drawn with the
configuration's trace shape) and simulator seed under the configuration's
periodic failures. Each seed gives every run the same sizes: the same grid,
the same number of ticks and of failures. Without ``pool_seed`` the seed
draws every trace and simulator seed. With it, the scenarios are drawn once
from ``pool_seed`` and the seed only orders them across the grid's rows:
every run then does the same work, for controllers whose programs take
shapes from the data (Demeter's GP and acquisition paths compile anew for
each new training-set size and candidate count, so new draws would put
minutes of compilation into every run's set-up).

Set-up builds the grid from the seed and runs it once whole, which traces
and compiles (or loads from the compile cache) every program the grid
uses. The window then runs the same grid again and again until
``seconds`` have passed, and ends when the sweep running at that moment
finishes: every sweep is the same work, so nothing compiles inside it.
The last sweep of the window is checked against the reference.

With ``trace`` the window is one sweep under the JAX profiler with the
program's spans on; the per-layer readers take their numbers from it.
"""
from __future__ import annotations

import gc
import json
import shutil
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import capture, check, harness, traffic, tracereduce
from bench.harness import log, now


def sweep_grid(config: Dict[str, Any], mix: Dict[str, Any],
               seed: int) -> Tuple[List[Any], Dict[str, Any]]:
    """The ``ScenarioSpec`` grid of a ``sweep`` mix, and what it was built
    from (rates ``[S, n]``, simulator seeds, controllers)."""
    from repro.dsp.sweep import ScenarioSpec
    from repro.dsp.workloads import PeriodicFailures, Trace

    if mix["kind"] != "sweep":
        raise ValueError(f"not a sweep mix: {mix['kind']!r}")
    ctls = list(mix["controllers"])
    per = int(config["scenarios_per_controller"])
    S = len(ctls) * per
    pool = mix.get("pool_seed")
    words = traffic.seed_words(seed if pool is None else pool, 2 * S)
    rates = traffic.rate_traces(config, words[:S])
    sim_seeds = [int(w) for w in words[S:]]
    if pool is not None:
        # the same scenarios for every seed, in the seed's order
        order = np.random.default_rng(
            traffic.seed_words(seed, 1)[0]).permutation(S)
        rates, sim_seeds = rates[order], [sim_seeds[i] for i in order]
    dt = float(config["dt_s"])
    fails = PeriodicFailures(float(config["failure_interval_s"]))
    specs = []
    for j in range(S):
        ctl = ctls[j // per]
        specs.append(ScenarioSpec(
            trace=Trace(rates=rates[j], dt_s=dt, name=config["name"]),
            controller=ctl, seed=sim_seeds[j], failures=fails,
            label=f"{config['name']}/{ctl}/{j}",
            forecaster=mix.get("forecaster", "arima")))
    iv = float(config["failure_interval_s"])
    return specs, {"rates": rates, "sim_seeds": sim_seeds,
                   "controllers": [s.controller for s in specs],
                   "fail_times": np.arange(iv, config["duration_s"], iv)}


def build_engine(config: Dict[str, Any], mix: Dict[str, Any], seed: int):
    from repro.core import DemeterHyperParams, EngineConfig
    from repro.dsp.simulator import ClusterModel
    from repro.dsp.sweep import SweepEngine

    specs, meta = sweep_grid(config, mix, seed)
    engine = SweepEngine(
        specs, model=ClusterModel(**config["cluster_model"]),
        config=EngineConfig(
            sim_backend=mix["engine"],
            hp=DemeterHyperParams(**config["demeter"]),
            decision_interval_s=config["baseline_decision_interval_s"]))
    return engine, meta


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, devs: Optional[list] = None) -> Dict[str, Any]:
    """One run of the cell; returns the result line's fields (``device``
    is filled from ``devs`` when given)."""
    config, mix = cell["config"], cell["mix"]
    name = cell["workload"]["name"]
    t_gen = now()
    engine, meta = build_engine(config, mix, seed)
    S, n = meta["rates"].shape
    log(f"traffic: {S} scenarios x {n} ticks built in {now() - t_gen!r} s")
    cap = capture.Capture()
    capture.install(cap, annotate=trace)

    clock = harness.CompileClock()
    res = engine.run()
    log(f"warm-up sweep: {S} scenarios x {n} ticks in "
        f"{res.wall_s!r} s, {clock.compiles} compiles "
        f"({clock.seconds!r} s), {res.n_model_fits} GP fits")
    # collect the warm-up's garbage in set-up, so that no collection of it
    # lands in the window
    gc.collect()
    setup_s = now() - t_start
    compiles0, comp_s0 = clock.compiles, clock.seconds
    gc_clock = harness.GcClock()

    spans = []
    tdir = harness.OUT / f"trace_{name}"
    if trace:
        import jax
        from jax.profiler import TraceAnnotation
        from repro import obs
        shutil.rmtree(tdir, ignore_errors=True)
        obs.enable(jax_profiler=True, clear=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.host_tracer_level = 1        # annotations, not runtime events
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        cap.reset()
        t0 = now()
        with TraceAnnotation(tracereduce.WINDOW):
            res = engine.run()
        elapsed = now() - t0
        jax.profiler.stop_trace()
        spans = list(obs.tracer().events)
        obs.disable()
        sweeps = 1
    else:
        walls = []
        t0 = now()
        while True:
            cap.reset()
            res = engine.run()
            walls.append(res.wall_s)
            if now() - t0 >= seconds:
                break
        elapsed = now() - t0
        sweeps = len(walls)
        log(f"sweep walls in the window: {walls!r}")
    window_compiles = clock.compiles - compiles0
    window_compile_s = clock.seconds - comp_s0
    clock.close()
    gc_clock.close()
    steps = sweeps * S * n
    log(f"window: {sweeps} sweeps, {steps} scenario-steps in "
        f"{elapsed!r} s; {window_compiles} compiles ({window_compile_s!r} s)"
        f" inside it; {gc_clock.count} full garbage collections "
        f"({gc_clock.seconds!r} s); step_interval {cap.step_interval_s!r} "
        f"s over {cap.intervals} intervals; last sweep {res.n_model_fits} "
        f"GP fits, "
        f"{res.n_forecast_updates} forecast updates, "
        f"{len(cap.decisions)} decisions")
    device = harness.device_info(devs) if devs else {}

    t_ref = now()
    checks, bad, info = check.sweep_checks(config, meta, res, cap)
    log(f"reference check of the last sweep took {now() - t_ref!r} s; "
        f"{len(bad)} scenario(s) disagree: {bad[:8]}; {info}")
    out: Dict[str, Any] = {
        "correct": check.passed(checks),
        "attempted": sweeps * S, "failed": len(bad)}
    if trace:
        loaded = tracereduce.load(tdir)
        reduced = tracereduce.reduce(loaded)
        (harness.OUT / f"trace_{name}.json").write_text(json.dumps(
            {"structure": loaded["structure"], "reduced": reduced},
            indent=1))
        log(f"trace: busy {reduced['busy_s']!r} s of "
            f"{reduced['window_s']!r} s window")
        ctx = {"trace": reduced, "spans": spans, "window_s": elapsed,
               "scenario_steps": steps,
               "step_interval_s": cap.step_interval_s}
        metrics = {}
        for m in cell["per_layer"]:
            v = harness.load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        out["breakdown"] = tracereduce.breakdown(reduced)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        log(f"elapsed window {elapsed!r} s")
        values = {"sweep_steps_per_s": steps / elapsed, "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell["end_to_end"]}
    out["device"] = device
    out["checks"] = checks
    return out
