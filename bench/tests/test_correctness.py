"""The comparison that decides ``correct``, at a tiny size on the CPU.

* the program as it is passes every number;
* the control (the reference one precision below the configuration's,
  put in the program's place) fails one of them;
* a run with the timed path broken underneath comes out not correct, for
  each fault a sweep cell can have: a step that returns its state
  unchanged, half of the batch left out, an answer altered where it is
  produced (here: a telemetry value, a forecast read, a GP fit's factor,
  a fit stopped short of its optimum, a fit that is not finite, a
  posterior read, an optimizing step's pick or its use of it, a
  profiling batch). The
  cells run on one chip, so no exchange between chips can be left out.

These drive the ``sweep`` kind's ``run`` itself (the chip guard is
``run.py``'s and is skipped here).
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench import capture, check, control, harness
from conftest import small_cell

BASE = ("tsw-sweep-baselines", 2, 3600.0)
DEMETER = ("ysb-sweep-demeter", 1, 7200.0)


def run_cell(spec, seed=2 ** 31 + 7):
    cell = small_cell(*spec)
    return harness.load_kind(cell).run(cell, seed, 0.0, False,
                                       time.perf_counter())


@pytest.fixture(scope="module")
def demeter_readings():
    return control.readings(small_cell(*DEMETER), 2 ** 32 + 3)


def test_sound_baseline_run_is_correct():
    out = run_cell(BASE)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert list(out["checks"]) == ["sim_rel_err", "table3_mismatched"]
    assert out["metrics"]["sweep_steps_per_s"]["value"] > 0


def test_sound_demeter_run_passes_and_its_control_fails(demeter_readings):
    r = demeter_readings
    assert r["gp_fits"] > 0 and r["program"]["gp_fits_unchecked"] == 0
    prog = {k: {"value": v, "limit": check.LIMITS[k]}
            for k, v in r["program"].items() if k in check.LIMITS}
    assert check.passed(prog), prog
    ctl = {k: {"value": v, "limit": check.LIMITS[k]}
           for k, v in r["control"].items() if k in check.LIMITS}
    assert not check.passed(ctl), ctl
    assert r["control"]["sim_rel_err"] > check.LIMITS["sim_rel_err"]
    assert r["control"]["forecast_rel_err"] > check.LIMITS["forecast_rel_err"]
    assert r["control"]["profile_pick_gap"] > check.LIMITS["profile_pick_gap"]
    assert r["program"]["gp_fits_nonfinite"] == 0
    assert r["program"]["picks"] > 0 and r["program"]["profiling_batches"] > 0


def _wrap_scan(monkeypatch, after):
    """Route the fused engine's scan through ``after(lag_in, carry, ms)``
    (``lag_in``: a copy of the consumer-lag state handed to the scan)."""
    import jax.numpy as jnp
    from repro.dsp import fused
    real = fused._fused_scan()

    def scan(model, lag, *args, **kw):
        lag_in = jnp.array(lag, copy=True)
        carry, ms = real(model, lag, *args, **kw)
        return after(lag_in, carry, ms)

    scan._cache_size = real._cache_size
    monkeypatch.setattr(fused, "_fused_scan", lambda: scan)


def test_state_returned_unchanged_is_caught(monkeypatch):
    def after(lag_in, carry, ms):
        return (lag_in,) + tuple(carry[1:]), ms

    _wrap_scan(monkeypatch, after)
    out = run_cell(BASE)
    assert not out["correct"]
    assert out["checks"]["sim_rel_err"]["value"] > 1e-3


def test_half_the_batch_left_out_is_caught(monkeypatch):
    def after(lag_in, carry, ms):
        half = {k: v.at[:, v.shape[1] // 2:].set(0) for k, v in ms.items()}
        return carry, half

    _wrap_scan(monkeypatch, after)
    out = run_cell(BASE)
    assert not out["correct"]
    assert out["failed"] >= 3


def test_altered_telemetry_is_caught(monkeypatch):
    def after(lag_in, carry, ms):
        lat = ms["latency"]
        return carry, {**ms, "latency": lat.at[0, 0].multiply(1 + 1e-5)}

    _wrap_scan(monkeypatch, after)
    out = run_cell(BASE)
    assert not out["correct"]
    assert out["checks"]["sim_rel_err"]["value"] > \
        check.LIMITS["sim_rel_err"]


def test_altered_forecast_is_caught(monkeypatch):
    from repro.core.forecast_bank import ForecastBank
    real = ForecastBank.binned_row

    def binned_row(self, row, horizon, bins):
        return real(self, row, horizon, bins) * 1.1

    monkeypatch.setattr(ForecastBank, "binned_row", binned_row)
    out = run_cell(DEMETER)
    assert not out["correct"]
    assert out["checks"]["forecast_rel_err"]["value"] > \
        check.LIMITS["forecast_rel_err"]


def test_altered_gp_fit_is_caught(monkeypatch):
    capture.install(capture.Capture())
    real = capture.ORIGINAL["fit_packed"]

    def fit(x, y, mask, t0s, max_iter):
        theta, val, chol, alpha = real(x, y, mask, t0s, max_iter=max_iter)
        return theta, val, chol, alpha.at[0, 0].add(1e-2)

    monkeypatch.setitem(capture.ORIGINAL, "fit_packed", fit)
    out = run_cell(DEMETER)
    assert not out["correct"]
    assert out["checks"]["gp_alpha_err"]["value"] > \
        check.LIMITS["gp_alpha_err"]


def _plant(monkeypatch, seam, wrap):
    """Put ``wrap(real)`` in the place of the program's ``seam`` under the
    capture."""
    capture.install(capture.Capture())
    monkeypatch.setitem(capture.ORIGINAL, seam, wrap(capture.ORIGINAL[seam]))


def test_fit_stopped_short_is_caught(monkeypatch):
    _plant(monkeypatch, "fit_packed",
           lambda real: lambda x, y, mask, t0s, max_iter: real(
               x, y, mask, t0s, max_iter=2))
    out = run_cell(DEMETER)
    assert not out["correct"]
    assert out["checks"]["gp_theta_gap"]["value"] > \
        check.LIMITS["gp_theta_gap"]


def test_nonfinite_fit_is_caught(monkeypatch):
    import jax.numpy as jnp

    def wrap(real):
        def fit(x, y, mask, t0s, max_iter):
            theta, val, chol, alpha = real(x, y, mask, t0s,
                                           max_iter=max_iter)
            return theta, val, chol, alpha.at[0, 0].set(jnp.nan)
        return fit

    _plant(monkeypatch, "fit_packed", wrap)
    out = run_cell(DEMETER)
    assert not out["correct"]
    assert out["checks"]["gp_alpha_err"]["value"] == np.inf


def test_altered_posterior_is_caught(monkeypatch):
    def wrap(real):
        def posterior(self, xq):
            mean, var = real(self, xq)
            return mean * (1 + 1e-2), var
        return posterior

    _plant(monkeypatch, "posterior", wrap)
    out = run_cell(DEMETER)
    assert not out["correct"]
    assert out["checks"]["gp_mean_err"]["value"] > \
        check.LIMITS["gp_mean_err"]


def test_altered_pick_is_caught(monkeypatch):
    def wrap(real):
        def pick(self, segment):
            out = real(self, segment)
            if out is None:
                return out
            j = self._configs.index(out[0])
            return self._configs[(j + 1) % len(self._configs)], out[1]
        return pick

    _plant(monkeypatch, "pick_config", wrap)
    out = run_cell(DEMETER)
    assert not out["correct"]
    assert out["checks"]["picks_mismatched"]["value"] > 0


def test_step_that_ignores_its_pick_is_caught(monkeypatch):
    def wrap(real):
        def step(self, metrics=None):
            et = self.hp.efficiency_threshold
            self.hp.efficiency_threshold = float("inf")
            try:
                return real(self, metrics)
            finally:
                self.hp.efficiency_threshold = et
        return step

    _plant(monkeypatch, "optimization_step", wrap)
    out = run_cell(DEMETER)
    assert not out["correct"]
    assert out["checks"]["opt_steps_mismatched"]["value"] > 0


def test_altered_profiling_batch_is_caught(monkeypatch):
    def wrap(real):
        def select(candidates, *args, **kw):
            n = len(candidates)
            return [(j + n // 2) % n for j in real(candidates, *args, **kw)]
        return select

    _plant(monkeypatch, "select_profiling_batch", wrap)
    out = run_cell(DEMETER)
    assert not out["correct"]
    assert out["checks"]["profile_pick_gap"]["value"] > \
        check.LIMITS["profile_pick_gap"]


def test_rel_err_floors_small_values():
    assert check.rel_err(np.array([1e-9]), np.array([0.0])) == \
        pytest.approx(1e-3)
    assert check.rel_err(np.array([np.nan]), np.array([1.0])) == np.inf
