"""Differential suite for the device-backed sweep engines.

Two layers:

* **in-process** — BatchState pad/unpad, direct
  ``ShardedSweepExecutor``-vs-``BatchedSweepExecutor`` and
  ``FusedSweepExecutor``-vs-``BatchedSweepExecutor`` step equivalence on
  whatever mesh the current process has (a 1-device mesh exercises the
  whole jitted/donated path), the fused engine's recompile budget
  (chunk-bucketed interval padding, with the un-bucketed failure mode
  seeded red through the contract checker), and ``EngineConfig`` device
  validation;
* **subprocess** — the full four-way fused/sharded/batched/scalar
  ``SweepResult`` equivalence under 1/2/4 *virtual* devices.
  ``xla_force_host_platform_device_count`` is latched at backend init, so
  each device count runs ``tests/helpers/sharded_diff.py`` in a fresh
  interpreter via the ``run_under_devices`` fixture (see
  ``tests/conftest.py``); ragged grids and active failure schedules are
  exercised there, and the worker also asserts the compiled sharded step
  and fused interval scan contain no cross-scenario collectives.
"""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import EngineConfig
from repro.dsp import (BatchedSweepExecutor, BatchState, ClusterModel,
                       FusedSweepExecutor, JobConfig, PeriodicFailures,
                       ShardedSweepExecutor, make_trace, run_sweep,
                       scenario_grid)

DIFF_SCRIPT = Path(__file__).parent / "helpers" / "sharded_diff.py"
MODEL = ClusterModel()


# ---------------------------------------------------------------------------
# BatchState pad / unpad
# ---------------------------------------------------------------------------

class TestBatchStatePadding:
    def test_roundtrip(self):
        configs = [JobConfig(workers=4), JobConfig(workers=9)]
        state = BatchState.from_configs(configs)
        state.lag_events[:] = [10.0, 20.0]
        state.downtime_left_s[:] = [0.0, 33.0]
        state.last_rate[:] = [40e3, 50e3]
        padded = state.pad(5)
        assert len(padded) == 5
        back = padded.unpad(2)
        for f in BatchState.FIELDS:
            np.testing.assert_array_equal(getattr(back, f),
                                          getattr(state, f))

    def test_pad_rows_are_fresh_cmax(self):
        padded = BatchState.from_configs([JobConfig(workers=4)]).pad(3)
        assert padded.config_of(1) == padded.config_of(2) == JobConfig()
        np.testing.assert_array_equal(padded.lag_events[1:], 0.0)
        np.testing.assert_array_equal(padded.downtime_left_s[1:], 0.0)

    def test_pad_same_size_is_identity(self):
        state = BatchState.from_configs([JobConfig()])
        assert len(state.pad(1)) == 1

    def test_pad_shrink_rejected(self):
        with pytest.raises(ValueError, match="pad"):
            BatchState.from_configs([JobConfig()] * 3).pad(2)

    def test_unpad_grow_rejected(self):
        with pytest.raises(ValueError, match="slice"):
            BatchState.from_configs([JobConfig()]).unpad(2)

    def test_unpad_copies(self):
        state = BatchState.from_configs([JobConfig()] * 2)
        view = state.unpad(1)
        view.lag_events[0] = 123.0
        assert state.lag_events[0] == 0.0


# ---------------------------------------------------------------------------
# direct executor equivalence (any mesh width, including 1)
# ---------------------------------------------------------------------------

class TestShardedExecutorEquivalence:
    """ShardedSweepExecutor must track BatchedSweepExecutor step-for-step
    through failures and reconfigurations; runs on however many devices the
    process has (the CI matrix leg gives it 4)."""

    def _pair(self, configs, seeds, n_steps):
        kw = dict(dt=5.0, n_steps=n_steps)
        return (BatchedSweepExecutor(MODEL, configs, seeds, **kw),
                ShardedSweepExecutor(MODEL, configs, seeds, **kw))

    def test_step_failure_reconfigure_equivalence(self):
        configs = [JobConfig(), JobConfig(workers=6), JobConfig(workers=4)]
        seeds = [0, 1, 2]
        n_steps = 240
        bat, sh = self._pair(configs, seeds, n_steps)
        assert sh.n_rows % sh.n_devices == 0
        rng = np.random.default_rng(42)
        big = JobConfig(workers=12)
        for i in range(n_steps):
            if i == 60:
                bat.inject_failure(1)
                sh.inject_failure(1)
            if i == 120:
                assert bat.reconfigure_one(2, big)
                assert sh.reconfigure_one(2, big)
            rates = rng.uniform(20_000, 70_000, len(configs))
            mb = bat.step(rates)
            ms = sh.step(rates)
            assert set(ms) == set(mb)
            for k in mb:
                np.testing.assert_allclose(ms[k], mb[k], rtol=1e-9,
                                           atol=1e-9, err_msg=k)
            np.testing.assert_array_equal(sh.caught_up(), bat.caught_up())
            np.testing.assert_array_equal(sh.workers(), bat.workers())
        np.testing.assert_array_equal(sh.reconf_count, bat.reconf_count)
        for k in bat.hist:
            np.testing.assert_allclose(sh.hist[k], bat.hist[k], rtol=1e-9,
                                       atol=1e-9, err_msg=k)

    def test_ragged_padding_matches_mesh(self):
        n = jax.device_count()
        configs = [JobConfig()] * (n + 1)
        sh = ShardedSweepExecutor(MODEL, configs, list(range(n + 1)),
                                  dt=5.0, n_steps=4)
        assert sh.n_rows == 2 * n
        m = sh.step(np.full(n + 1, 50_000.0))
        assert all(v.shape == (n + 1,) for v in m.values())

    def test_noop_reconfigure_not_counted(self):
        sh = ShardedSweepExecutor(MODEL, [JobConfig()], [0], dt=5.0,
                                  n_steps=4)
        assert not sh.reconfigure_one(0, JobConfig())
        assert sh.reconf_count[0] == 0

    def test_compiled_step_satisfies_contract(self):
        # The zero-collectives invariant (plus donation, dtype ceiling and
        # the no-callback rule) lives in SHARDED_STEP_CONTRACT now, checked
        # through the same probe scripts/check_contracts.py runs.
        from repro.analysis.contracts import run_probe

        sh = ShardedSweepExecutor(MODEL, [JobConfig()] * 4, [0, 1, 2, 3],
                                  dt=5.0, n_steps=4)
        report = run_probe(sh.contract_probe())
        assert report.ok, report.summary()
        assert report.n_primitives > 0      # a real lowering, not host_only


# ---------------------------------------------------------------------------
# direct fused-executor equivalence (any mesh width, including 1)
# ---------------------------------------------------------------------------

class TestFusedExecutorEquivalence:
    """FusedSweepExecutor must track BatchedSweepExecutor both through
    tick-at-a-time :meth:`step` (one-tick intervals) and through
    :meth:`step_interval` with a precomputed injection mask — the two
    stepping surfaces the sweep engine drives."""

    def _pair(self, configs, seeds, n_steps):
        kw = dict(dt=5.0, n_steps=n_steps)
        return (BatchedSweepExecutor(MODEL, configs, seeds, **kw),
                FusedSweepExecutor(MODEL, configs, seeds, **kw))

    def test_step_failure_reconfigure_equivalence(self):
        configs = [JobConfig(), JobConfig(workers=6), JobConfig(workers=4)]
        seeds = [0, 1, 2]
        n_steps = 120
        bat, fu = self._pair(configs, seeds, n_steps)
        assert fu.n_rows % fu.n_devices == 0
        rng = np.random.default_rng(42)
        big = JobConfig(workers=12)
        for i in range(n_steps):
            if i == 30:
                bat.inject_failure(1)
                fu.inject_failure(1)
            if i == 60:
                assert bat.reconfigure_one(2, big)
                assert fu.reconfigure_one(2, big)
            rates = rng.uniform(20_000, 70_000, len(configs))
            mb = bat.step(rates)
            mf = fu.step(rates)
            assert set(mf) == set(mb)
            for k in mb:
                np.testing.assert_allclose(mf[k], mb[k], rtol=1e-9,
                                           atol=1e-9, err_msg=k)
            np.testing.assert_array_equal(fu.caught_up(), bat.caught_up())
            np.testing.assert_array_equal(fu.workers(), bat.workers())
        np.testing.assert_array_equal(fu.reconf_count, bat.reconf_count)
        for k in bat.hist:
            np.testing.assert_allclose(fu.hist[k], bat.hist[k], rtol=1e-9,
                                       atol=1e-9, err_msg=k)

    def test_tick_choice_is_explicit(self):
        # The float64 carry runs the jnp tick on every backend; asking for
        # the Pallas tick is refused at construction (Mosaic lowers no
        # float64 kernel), never discovered as a compile error on the chip.
        fu = FusedSweepExecutor(MODEL, [JobConfig()], [0], dt=5.0, n_steps=4)
        assert not fu.use_pallas
        assert fu.tick == "jnp:kernels.ref.fused_tick_ref"
        with pytest.raises(ValueError, match="float32 carry"):
            FusedSweepExecutor(MODEL, [JobConfig()], [0], dt=5.0, n_steps=4,
                               use_pallas=True)

    def test_interval_with_injection_mask_matches_ticked_batched(self):
        # One K-tick scan dispatch with failures marked in the [K, S] mask
        # == K batched steps with inject_failure called after the marked
        # ticks (the exact spot the sweep engine's per-tick loop calls it).
        configs = [JobConfig(workers=4), JobConfig(workers=8)]
        K = 24
        bat, fu = self._pair(configs, [0, 1], K + 4)  # +4 carry-over ticks
        rng = np.random.default_rng(7)
        rates = rng.uniform(20_000, 70_000, (K, 2))
        inject = np.zeros((K, 2), bool)
        inject[5, 1] = True
        inject[17, 0] = True
        inject[23, 1] = True        # last tick: rollback carries over
        ms = fu.step_interval(rates, inject)
        for k in range(K):
            mb = bat.step(rates[k])
            for key in mb:
                np.testing.assert_allclose(ms[key][k], mb[key], rtol=1e-9,
                                           atol=1e-9,
                                           err_msg=f"{key} @ tick {k}")
            for j in np.nonzero(inject[k])[0]:
                bat.inject_failure(int(j))  # fused staged these via the mask
        np.testing.assert_array_equal(fu.caught_up(), bat.caught_up())
        for key in bat.hist:
            np.testing.assert_allclose(fu.hist[key], bat.hist[key],
                                       rtol=1e-9, atol=1e-9, err_msg=key)
        # the tick-23 injection was staged across the interval boundary:
        # the next dispatch must fold its rollback into the first tick
        r2 = rng.uniform(20_000, 70_000, (4, 2))
        m2 = fu.step_interval(r2)
        for k in range(4):
            mb = bat.step(r2[k])
            for key in mb:
                np.testing.assert_allclose(m2[key][k], mb[key], rtol=1e-9,
                                           atol=1e-9,
                                           err_msg=f"{key} @ carry tick {k}")

    def test_compiled_interval_scan_satisfies_contract(self):
        # Donation, zero collectives, no callbacks in the scan body, the
        # dtype ceiling and the <=2-trace budget all live in
        # FUSED_INTERVAL_CONTRACT, checked through the same probe
        # scripts/check_contracts.py runs.
        from repro.analysis.contracts import run_probe

        fu = FusedSweepExecutor(MODEL, [JobConfig()] * 3, [0, 1, 2],
                                dt=5.0, n_steps=4)
        report = run_probe(fu.contract_probe())
        assert report.ok, report.summary()
        assert report.n_primitives > 0      # a real lowering, not host_only
        assert report.n_traces is not None and report.n_traces <= 2


# ---------------------------------------------------------------------------
# fused recompile budget (chunk bucketing) — green and seeded red
# ---------------------------------------------------------------------------

class TestFusedRecompileBudget:
    """A sweep over mixed interval lengths and scenario counts must compile
    the fused interval scan at most twice (once per scenario-axis width):
    interval K is padded to the smallest ``chunk * 2**m >= K`` with padding
    ticks masked out, so distinct Ks share traces. Dropping that bucketing
    is the seeded-red case — one trace per raw K — and the contract checker
    must flag it as a ``max_traces`` violation."""

    JIT_KW = dict(static_argnames=("model", "dt", "use_pallas"),
                  donate_argnums=(1, 2, 3, 4, 5))

    def test_bucketed_workload_stays_within_budget(self):
        from repro.analysis.contracts import count_traces
        from repro.dsp.fused import (FUSED_INTERVAL_CONTRACT,
                                     fused_interval_scan, interval_arg_sets)
        n = count_traces(fused_interval_scan, interval_arg_sets(),
                         x64=True, **self.JIT_KW)
        assert FUSED_INTERVAL_CONTRACT.max_traces == 2
        assert n <= 2, f"bucketed workload compiled {n} traces"

    def test_unbucketed_workload_seeds_red(self):
        # chunk=None lowers the *raw* interval lengths — one trace per
        # distinct K. The checker (not this test's arithmetic) must turn
        # that into a max_traces violation, proving the analyzer catches
        # the regression before it reaches a sweep.
        from repro.analysis.contracts import count_traces, run_probe
        from repro.dsp.fused import fused_interval_scan, interval_arg_sets

        fu = FusedSweepExecutor(MODEL, [JobConfig(), JobConfig()], [0, 1],
                                dt=5.0, n_steps=4)
        probe = fu.contract_probe()
        probe.traces = lambda: count_traces(
            fused_interval_scan, interval_arg_sets(chunk=None),
            x64=True, **self.JIT_KW)
        report = run_probe(probe)
        assert not report.ok
        # one trace per distinct raw K (count_traces reports cache growth,
        # so shapes another test already lowered may be absorbed — the
        # budget is still blown)
        assert report.n_traces is not None and report.n_traces > 2
        assert [v.field for v in report.violations] == ["max_traces"]


# ---------------------------------------------------------------------------
# EngineConfig device placement validation
# ---------------------------------------------------------------------------

class TestEngineConfigDevices:
    @pytest.mark.parametrize("bad", [0, -1, 2.5, True, "two"])
    def test_rejects_non_positive_int_devices(self, bad):
        with pytest.raises(ValueError, match="devices"):
            EngineConfig(devices=bad)

    def test_rejects_more_devices_than_visible(self):
        with pytest.raises(ValueError,
                           match="xla_force_host_platform_device_count"):
            EngineConfig(devices=jax.device_count() + 1)

    def test_rejects_sharded_on_one_explicit_device(self):
        with pytest.raises(ValueError, match="at least 2 devices"):
            EngineConfig(sim_backend="sharded", devices=1)

    def test_devices_accepted_up_to_visible(self):
        cfg = EngineConfig(devices=jax.device_count())
        assert cfg.devices == jax.device_count()

    def test_single_device_sharded_rejected_in_subprocess(
            self, run_under_devices):
        # Deterministic regardless of this process's device count: a fresh
        # interpreter with exactly one visible device must reject
        # sim_backend="sharded" with the actionable message.
        out = run_under_devices(1, DIFF_SCRIPT, "--case", "reject")
        assert "REJECT-OK" in out


# ---------------------------------------------------------------------------
# full differential runs under 1/2/4 virtual devices (subprocesses)
# ---------------------------------------------------------------------------

class TestEngineDifferential:
    """Four-way fused/sharded/batched/scalar differential; the devices=1
    legs exercise the fused engine without a mesh (sharded is skipped
    there — it requires >= 2 devices)."""

    @pytest.mark.parametrize("case,devices", [
        ("uniform", 1),
        ("uniform", 2),
        ("ragged", 1),
        ("ragged", 2),
        ("ragged", 4),
    ])
    def test_engines_match_batched_and_scalar(self, run_under_devices,
                                              case, devices):
        out = run_under_devices(devices, DIFF_SCRIPT,
                                "--case", case, "--devices", devices)
        assert f"DIFF-OK case={case} devices={devices}" in out

    @pytest.mark.slow
    def test_demeter_engines_match_batched(self, run_under_devices):
        # Demeter controllers on the device engines: shared GP + forecast
        # banks dispatch over the same scenario mesh / interval driver.
        out = run_under_devices(4, DIFF_SCRIPT,
                                "--case", "demeter", "--devices", 4)
        assert "DIFF-OK case=demeter devices=4" in out


# ---------------------------------------------------------------------------
# in-process end-to-end when this process already has a mesh (CI matrix leg)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices in-process (run under "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           "count=4)")
class TestShardedInProcess:
    def test_run_sweep_sharded_default_devices(self):
        traces = [make_trace(k, duration_s=600.0, dt_s=5.0)
                  for k in ("diurnal", "flash")]
        grid = scenario_grid(traces, ("static", "reactive"), (0,),
                             failures=PeriodicFailures(300.0))
        sharded = run_sweep(grid, config=EngineConfig(sim_backend="sharded"))
        batched = run_sweep(grid)
        assert sharded.engine == "sharded"
        for a, b in zip(sharded.scenarios, batched.scenarios):
            assert a.allclose(b), f"{a.name} diverged"

    def test_run_sweep_fused_default_devices(self):
        traces = [make_trace(k, duration_s=600.0, dt_s=5.0)
                  for k in ("diurnal", "flash")]
        grid = scenario_grid(traces, ("static", "reactive"), (0,),
                             failures=PeriodicFailures(300.0))
        fused = run_sweep(grid, config=EngineConfig(sim_backend="fused"))
        batched = run_sweep(grid)
        assert fused.engine == "fused"
        for a, b in zip(fused.scenarios, batched.scenarios):
            assert a.allclose(b), f"{a.name} diverged"
