"""Tests for ``repro.obs`` — tracing, metrics, exporters, and the
observability guarantees the rest of the repo depends on:

* the disabled path is a shared no-op singleton (no per-call allocation);
* enabling obs never perturbs sweep results (bit-identical digests);
* the overhead of instrumentation on the fused smoke case is bounded;
* the Chrome-trace / bench exporters round-trip and the bench differ
  flags real regressions while tolerating noise;
* the zero-ops contract probe actually fails when instrumentation leaks
  an op into the traced computation.
"""
from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import obs

REPO = Path(__file__).resolve().parent.parent
OBS_REPORT = REPO / "scripts" / "obs_report.py"


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with obs disabled and cleared."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# trace.py
# ---------------------------------------------------------------------------

class TestTrace:
    def test_disabled_span_is_shared_singleton(self):
        a = obs.span("x", k=1)
        b = obs.span("y")
        assert a is b, "disabled span() must return one shared no-op"
        with a:
            pass
        assert not obs.tracer().events

    def test_disabled_metrics_do_not_record(self):
        obs.inc("c", 5)
        obs.set_gauge("g", 1.0)
        obs.add_phase("simulate", 0.5)
        obs.track_jit_cache("f", 2)
        assert obs.snapshot() == {"counters": {}, "gauges": {}}

    def test_span_nesting_depths(self):
        obs.enable(clear=True)
        with obs.span("outer"):
            with obs.span("inner"):
                with obs.span("leaf", tag=3):
                    pass
            with obs.span("inner2"):
                pass
        obs.disable()
        recs = {r.name: r for r in obs.tracer().events}
        assert recs["outer"].depth == 0
        assert recs["inner"].depth == 1
        assert recs["leaf"].depth == 2
        assert recs["inner2"].depth == 1
        assert recs["leaf"].attrs == {"tag": 3}
        # children complete before parents; durations nest
        assert recs["outer"].dur_ns >= recs["inner"].dur_ns

    def test_timestamps_monotonic_ns(self):
        obs.enable(clear=True)
        with obs.span("a"):
            time.sleep(0.001)
        with obs.span("b"):
            pass
        obs.disable()
        a, b = obs.tracer().events
        assert a.dur_ns >= 1_000_000          # slept >= 1 ms
        assert b.ts_ns >= a.ts_ns + a.dur_ns  # b started after a ended

    def test_enabled_scope_restores(self):
        assert not obs.enabled()
        with obs.force_enabled():
            assert obs.enabled()
        assert not obs.enabled()
        obs.enable()
        with obs.trace.force_disabled():
            assert not obs.enabled()
        assert obs.enabled()

    def test_max_events_drops_are_counted(self):
        tr = obs.trace.Tracer(max_events=2)
        for i in range(5):
            with tr.span(f"s{i}", {}):
                pass
        assert len(tr.events) == 2
        assert tr.dropped == 3


# ---------------------------------------------------------------------------
# metrics.py
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_histogram(self):
        obs.enable(clear=True)
        obs.inc("sweep.ticks")
        obs.inc("sweep.ticks", 4)
        obs.set_gauge("g", 2.5)
        obs.set_gauge("g", 3.5)               # last value wins
        obs.registry().gauge("unset")         # never set: left out
        obs.disable()
        obs.inc("sweep.ticks")                # disabled: not counted
        assert obs.snapshot() == {"counters": {"sweep.ticks": 5},
                                  "gauges": {"g": 3.5}}

    def test_track_jit_cache_counts_growth_only(self):
        obs.enable(clear=True)
        obs.track_jit_cache("f", 1)
        obs.track_jit_cache("f", 1)           # no growth
        obs.track_jit_cache("f", 3)           # +2
        obs.disable()
        snap = obs.snapshot()
        assert snap["counters"]["recompiles.f"] == 3
        assert snap["gauges"]["jit_cache.f"] == 3

    def test_timed_phase_accumulates(self):
        obs.enable(clear=True)
        with obs.timed_phase("simulate", "spanname"):
            time.sleep(0.001)
        obs.disable()
        snap = obs.snapshot()
        assert snap["counters"]["phase.simulate_wall_s"] >= 0.001
        assert obs.tracer().events[0].name == "spanname"

    def test_timed_phase_disabled_is_singleton(self):
        a = obs.timed_phase("simulate", "x")
        b = obs.timed_phase("fit", "y")
        assert a is b


# ---------------------------------------------------------------------------
# export.py
# ---------------------------------------------------------------------------

class TestExport:
    def test_chrome_trace_round_trip(self, tmp_path):
        obs.enable(clear=True)
        with obs.span("sweep.run", engine="fused"):
            with obs.span("engine.step"):
                pass
        obs.inc("sweep.ticks", 7)
        obs.disable()
        path = tmp_path / "trace.json"
        obs.write_chrome_trace(str(path))
        doc = json.loads(path.read_text())
        assert doc["otherData"]["schema"] == obs.TRACE_SCHEMA
        events = doc["traceEvents"]
        assert [e["name"] for e in events] == ["engine.step", "sweep.run"]
        for e in events:
            assert e["ph"] == "X"
            assert e["dur"] >= 0 and e["ts"] >= 0    # micros
        assert events[1]["args"]["engine"] == "fused"
        assert events[0]["args"]["depth"] == 1
        assert events[0]["cat"] == "engine"
        counters = doc["otherData"]["metrics"]["counters"]
        assert counters["sweep.ticks"] == 7

    def test_merge_bench_and_schema(self, tmp_path):
        path = str(tmp_path / "bench.json")
        leg = obs.make_leg(engine="fused", devices=2, seed=0, mode="smoke",
                           scenarios=4, scenario_steps_per_s=1000.0)
        obs.merge_bench(path, "sweep_scaling", [leg], params={"dt": 5.0})
        obs.merge_bench(path, "other", [obs.make_leg(
            engine="batched", devices=1, seed=1)])
        doc = obs.load_bench(path)
        assert doc["schema"] == obs.BENCH_SCHEMA
        assert set(doc["benches"]) == {"sweep_scaling", "other"}
        assert doc["benches"]["sweep_scaling"]["params"] == {"dt": 5.0}

    def test_load_bench_rejects_wrong_schema(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text('{"schema": "other/9", "benches": {}}')
        with pytest.raises(ValueError, match="unsupported bench schema"):
            obs.load_bench(str(p))

    def _doc(self, sps: float):
        leg = obs.make_leg(engine="fused", devices=1, seed=0, mode="smoke",
                           scenarios=4, scenario_steps_per_s=sps)
        return {"schema": obs.BENCH_SCHEMA,
                "benches": {"b": {"legs": [leg]}}}

    def test_diff_flags_30pct_regression(self):
        rows, n = obs.diff_bench(self._doc(1000.0), self._doc(700.0))
        assert n == 1
        assert rows[0]["status"] == "REGRESSION"

    def test_diff_tolerates_10pct_noise(self):
        rows, n = obs.diff_bench(self._doc(1000.0), self._doc(900.0))
        assert n == 0
        assert rows[0]["status"] == "ok"

    def test_diff_new_leg_is_not_regression(self):
        rows, n = obs.diff_bench({"schema": obs.BENCH_SCHEMA, "benches": {}},
                                 self._doc(1.0))
        assert n == 0
        assert rows[0]["status"] == "new"


# ---------------------------------------------------------------------------
# scripts/obs_report.py CLI
# ---------------------------------------------------------------------------

class TestObsReportCLI:
    def _write(self, tmp_path, name, sps):
        leg = obs.make_leg(engine="fused", devices=1, seed=0, mode="smoke",
                           scenarios=4, scenario_steps_per_s=sps)
        p = tmp_path / name
        p.write_text(json.dumps({"schema": obs.BENCH_SCHEMA,
                                 "benches": {"b": {"legs": [leg]}}}))
        return str(p)

    def _run(self, *argv):
        return subprocess.run([sys.executable, str(OBS_REPORT), *argv],
                              capture_output=True, text=True)

    def test_diff_exit_nonzero_on_regression(self, tmp_path):
        old = self._write(tmp_path, "old.json", 1000.0)
        new = self._write(tmp_path, "new.json", 700.0)
        proc = self._run("--diff", old, new)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "REGRESSION" in proc.stdout

    def test_diff_exit_zero_within_tolerance(self, tmp_path):
        old = self._write(tmp_path, "old.json", 1000.0)
        new = self._write(tmp_path, "new.json", 900.0)
        proc = self._run("--diff", old, new)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_diff_rel_tol_flag(self, tmp_path):
        old = self._write(tmp_path, "old.json", 1000.0)
        new = self._write(tmp_path, "new.json", 900.0)
        proc = self._run("--diff", old, new, "--rel-tol", "0.05")
        assert proc.returncode == 1

    def test_summarize_trace(self, tmp_path):
        obs.enable(clear=True)
        with obs.span("sweep.run"):
            with obs.span("engine.fused.interval"):
                pass
        obs.track_jit_cache("fused_scan", 1)
        obs.disable()
        path = tmp_path / "trace.json"
        obs.write_chrome_trace(str(path))
        proc = self._run(str(path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "sweep.run" in proc.stdout
        assert "recompiles.fused_scan" in proc.stdout


# ---------------------------------------------------------------------------
# sweep integration: results unperturbed, spans present, overhead bounded
# ---------------------------------------------------------------------------

def _smoke_specs():
    from repro.dsp import PeriodicFailures, ScenarioSpec, make_trace
    return [ScenarioSpec(trace=make_trace("diurnal", duration_s=300.0,
                                          dt_s=5.0),
                         controller="reactive", seed=s,
                         failures=PeriodicFailures(120.0))
            for s in range(3)]


class TestSweepIntegration:
    def test_obs_off_and_on_bit_identical(self):
        from repro.core import EngineConfig
        from repro.dsp import run_sweep
        sys.path.insert(0, str(REPO / "tests" / "helpers"))
        from sharded_diff import VOLATILE

        specs = _smoke_specs()
        config = EngineConfig(sim_backend="fused")
        off = run_sweep(specs, config=config)
        obs.enable(clear=True)
        try:
            on = run_sweep(specs, config=config)
        finally:
            obs.disable()

        def strip(js):
            return {k: v for k, v in js.items() if k not in VOLATILE}

        assert strip(on.to_json()) == strip(off.to_json())
        names = {r.name for r in obs.tracer().events}
        assert "sweep.run" in names
        assert "engine.fused.interval" in names
        counters = obs.snapshot()["counters"]
        assert counters["sweep.ticks"] == off.n_steps
        assert counters["sweep.intervals"] >= 1

    def test_compile_wall_split_fields(self):
        from repro.core import EngineConfig
        from repro.dsp import run_sweep

        res = run_sweep(_smoke_specs(), config=EngineConfig())
        js = res.to_json()
        assert js["model_update_compile_wall_s"] >= 0.0
        assert js["forecast_update_compile_wall_s"] >= 0.0
        # steady-state walls exclude the compile share by construction
        assert js["forecast_update_wall_s"] >= 0.0
        assert js["model_update_wall_s"] >= 0.0

    def test_forecast_compile_split_on_cold_bank(self):
        """A cold-process ForecastBank books its first (compiling)
        dispatch into compile_wall_s, not the steady-state wall."""
        proc = subprocess.run(
            [sys.executable, "-c", (
                "import numpy as np\n"
                "from repro.core.forecast_bank import ForecastBank\n"
                "bank = ForecastBank(['arima'], horizon=12)\n"
                "v = bank.view(0)\n"
                "for t in range(40):\n"
                "    v.update(100.0 + t)\n"
                "bank.flush()\n"
                "assert bank.compile_wall_s > 0, bank.compile_wall_s\n"
                "assert bank.compile_wall_s > bank.update_wall_s\n"
                "print('SPLIT-OK')\n")],
            capture_output=True, text=True,
            env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
                 "HOME": "/tmp"},
            cwd=str(REPO))
        assert "SPLIT-OK" in proc.stdout, proc.stdout + proc.stderr

    def test_overhead_bound_on_fused_smoke(self):
        """Instrumentation overhead on the fused hot loop stays under 2%
        (plus an absolute slack for timer noise on shared runners)."""
        from repro.dsp.fused import FusedSweepExecutor
        from repro.dsp.simulator import ClusterModel, JobConfig

        def run_once(ex):
            t0 = time.perf_counter()
            ex.step_interval(np.full((16, 4), 1000.0))
            return time.perf_counter() - t0

        def make_ex():
            return FusedSweepExecutor(
                ClusterModel(), [JobConfig()] * 4, seeds=range(4),
                dt=5.0, n_steps=16 * 8)

        ex = make_ex()
        run_once(ex)                       # warm the jit cache
        best_off, best_on = np.inf, np.inf
        for _ in range(5):
            ex = make_ex()
            best_off = min(best_off, run_once(ex))
            ex = make_ex()
            obs.enable(clear=True)
            try:
                best_on = min(best_on, run_once(ex))
            finally:
                obs.disable()
        # 2% relative + 2ms absolute: span cost is ~µs per interval, the
        # absolute slack absorbs scheduler noise on short walls.
        assert best_on <= best_off * 1.02 + 2e-3, \
            f"obs overhead too high: {best_off:.6f}s -> {best_on:.6f}s"


# ---------------------------------------------------------------------------
# the interval step's spans: they tile it, and sit on the profiler's clock
# ---------------------------------------------------------------------------

STEP_SPANS = ("engine.fused.prepare", "engine.fused.interval",
              "engine.fused.readback")


@pytest.fixture(scope="module")
def clock_sweep():
    """A cut grid, one Demeter and one baseline scenario on the fused
    engine, run once with obs off: the engine and its result. 42 min at
    dt 5 s reach Demeter's first profiling round (t = 2400 s at a 600 s
    profile interval), which fits GPs and builds RGP ensembles; a failure
    every 20 min exercises the staged injections."""
    from repro.core import DemeterHyperParams, EngineConfig
    from repro.dsp import PeriodicFailures, ScenarioSpec, make_trace
    from repro.dsp.sweep import SweepEngine

    trace = make_trace("diurnal", duration_s=2520.0, dt_s=5.0)
    specs = [ScenarioSpec(trace=trace, controller=ctl, seed=seed,
                          failures=PeriodicFailures(1200.0))
             for ctl, seed in (("demeter", 0), ("reactive", 1))]
    eng = SweepEngine(specs, config=EngineConfig(
        sim_backend="fused", hp=DemeterHyperParams(profile_interval_s=600.0)))
    obs.disable()
    return eng, eng.run()


class TestIntervalSpans:
    def test_spans_tile_step_interval_and_leave_results_alone(
            self, clock_sweep, monkeypatch):
        """The three step spans cover 90-100 % of the time spent inside
        ``step_interval``; what they leave is the call, the span records
        and freeing the step's arrays on return. A pause of the garbage
        collector between two spans counts in the call and in no span, so
        the sweep runs three times and the best covered share is judged."""
        from repro.dsp.fused import FusedSweepExecutor
        sys.path.insert(0, str(REPO / "tests" / "helpers"))
        from sharded_diff import VOLATILE

        eng, off = clock_sweep
        inside = [0]
        step0 = FusedSweepExecutor.step_interval

        def timed_step(self, rates_ks, inject_ks=None):
            t0 = time.perf_counter_ns()
            try:
                return step0(self, rates_ks, inject_ks)
            finally:
                inside[0] += time.perf_counter_ns() - t0

        def strip(js):
            return {k: v for k, v in js.items() if k not in VOLATILE}

        monkeypatch.setattr(FusedSweepExecutor, "step_interval", timed_step)
        shares = []
        for _ in range(3):
            inside[0] = 0
            obs.enable(clear=True)
            try:
                on = eng.run()
            finally:
                obs.disable()
            assert strip(on.to_json()) == strip(off.to_json())
            spans = obs.tracer().events
            counts = {n: sum(s.name == n for s in spans) for n in STEP_SPANS}
            assert len(set(counts.values())) == 1, counts
            shares.append(sum(s.dur_ns for s in spans
                              if s.name in STEP_SPANS) / inside[0])
        assert off.n_model_fits > 0
        assert {"sweep.model_refresh", "demeter.ensemble"} <= \
            {s.name for s in spans}
        # every RGPE read is packed; the scalar-read counter is there at 0
        counters = obs.snapshot()["counters"]
        assert counters["gp.packed_reads"] > 0
        assert counters["gp.single_reads"] == 0
        assert 0.90 <= max(shares) and max(shares) <= 1.0, shares

    def test_spans_agree_with_the_profiler_clock(self, clock_sweep,
                                                  tmp_path):
        """Every span is also an annotation on the profiler's host plane,
        with the same duration and a constant offset between the clocks.
        A preemption between the two clocks' stamps of one boundary lands
        in one clock only, so the sweep is profiled three times and each
        span is judged by its closest reading."""
        import jax
        from jax.profiler import ProfileData

        eng, _ = clock_sweep
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # as the benchmark's traced run
        opts.host_tracer_level = 1
        readings = []                     # per run: [(name, dur, dur', off)]
        for run in range(3):
            obs.enable(jax_profiler=True, clear=True)
            out = tmp_path / str(run)
            jax.profiler.start_trace(str(out), profiler_options=opts)
            try:
                eng.run()
            finally:
                jax.profiler.stop_trace()
                obs.disable()
            spans = obs.tracer().events
            names = {s.name for s in spans}
            assert {*STEP_SPANS, "sweep.run", "sweep.policy_block",
                    "sweep.model_refresh", "demeter.ensemble"} <= names

            (pb,) = out.glob("plugins/profile/*/*.xplane.pb")
            host = next(p for p in ProfileData.from_file(str(pb)).planes
                        if p.name == "/host:CPU")
            theirs = {n: [] for n in names}
            for line in host.lines:
                for ev in line.events:
                    if ev.name in theirs:
                        theirs[ev.name].append((ev.start_ns,
                                                ev.end_ns - ev.start_ns))
            rows = []
            for name in sorted(names):
                mine = sorted((s.ts_ns, s.dur_ns) for s in spans
                              if s.name == name)
                assert len(theirs[name]) == len(mine), name
                rows += [(name, d0, d1, t1 - t0) for (t0, d0), (t1, d1)
                         in zip(mine, sorted(theirs[name]))]
            mid = float(np.median([r[3] for r in rows]))
            readings.append([(n, d0, d1, off - mid)
                             for n, d0, d1, off in rows])
        assert len({len(r) for r in readings}) == 1
        devs = []
        for per_run in zip(*readings):
            assert len({r[0] for r in per_run}) == 1
            name, d0, d1, _ = min(per_run, key=lambda r: abs(r[2] - r[1]))
            assert abs(d1 - d0) <= max(0.02 * d1, 20_000), (name, d0, d1)
            devs.append(min((r[3] for r in per_run), key=abs))
        assert max(devs) - min(devs) <= 50_000


class TestProgramNames:
    """The benchmark's device-trace readers find programs by XLA module
    name; a rename of either program must fail here, not turn a per-layer
    metric silently to null."""

    @staticmethod
    def _read(metric, module_name):
        sys.path.insert(0, str(REPO))
        from bench import harness, tracereduce
        event = f"{module_name}(7)"          # as the XLA Modules line
        ctx = {"trace": {"window_s": 1.0, "programs": {
            tracereduce.program_name(event): 0.25}},
            "scenario_steps": 1000}
        return harness.load_reader(metric)(ctx)

    @staticmethod
    def _module_name(lowered):
        import re
        return re.search(r"module @(\S+)", lowered.as_text()).group(1)

    def test_fit_program_name(self):
        import jax.numpy as jnp
        from repro.core.gp_bank import _fit_packed
        B, n, d, R = 2, 4, 5, 2
        lowered = _fit_packed.lower(
            jnp.zeros((B, n, d)), jnp.zeros((B, n)), jnp.ones((B, n)),
            jnp.zeros((B, R, d + 2)), max_iter=3)
        name = self._module_name(lowered)
        assert name == "jit__fit_packed"
        assert self._read("gp_fit_device_share.sweep", name) == 25.0

    def test_interval_scan_program_name(self):
        from repro.dsp import ClusterModel, FusedSweepExecutor, JobConfig
        ex = FusedSweepExecutor(ClusterModel(), [JobConfig()] * 2, [0, 1],
                                dt=5.0, n_steps=16)
        name = self._module_name(ex.lower_interval(16))
        assert name == "jit_fused_interval_scan"
        assert self._read("sim_scan_ms_per_kstep", name) == 250.0


# ---------------------------------------------------------------------------
# the zero-ops probe actually catches leaks
# ---------------------------------------------------------------------------

class TestInstrumentationProbe:
    def test_clean_function_passes(self):
        import jax.numpy as jnp
        from repro.analysis.contracts import run_probe

        def f(x):
            with obs.span("f"):
                return jnp.sin(x) + 1.0

        probe = obs.instrumentation_probe("test:clean", f,
                                          (np.ones(4),))
        report = run_probe(probe)
        assert report.ok, report.violations

    def test_leaky_instrumentation_fails(self):
        """If an obs call site ever contributes a traced op, the pinned
        primitive budget is exceeded and the probe goes red."""
        import jax.numpy as jnp
        from repro.analysis.contracts import run_probe

        def f(x):
            y = jnp.sin(x)
            if obs.enabled():              # leak: extra ops when obs is on
                y = y + jnp.cos(x) * 2.0
            return y

        probe = obs.instrumentation_probe("test:leaky", f,
                                          (np.ones(4),))
        report = run_probe(probe)
        assert not report.ok
        assert any(v.field == "max_primitives" for v in report.violations)
