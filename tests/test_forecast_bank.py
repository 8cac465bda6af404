"""ForecastBank / DetectorBank agreement with the scalar zoo oracles.

The batched jitted paths never replace the float64 NumPy reference
implementations — they are pinned against them: same updates, same
rollouts, same binned-forecast decisions, same anomaly flags/episodes.
Property-based variants (random orders, forgetting factors, NaN streams)
live in ``test_forecast_bank_props.py`` behind the optional ``hypothesis``
dependency.
"""
import numpy as np
import pytest

from repro.core import (DetectorBank, ForecastBank, HoltWinters,
                        MetricDetector, OnlineARIMA, RecoveryTracker,
                        SeasonalNaive, binned_forecast, make_forecaster)
from repro.core.anomaly import DETECTOR_ERR_WINDOW
from repro.core.forecast import ERR_WINDOW, FORECASTER_KINDS


def feed(values, *models):
    for v in values:
        for m in models:
            m.update(v)


def sine_stream(n, level=50.0, amp=10.0, period=17.0, noise=0.5, seed=0):
    rng = np.random.default_rng(seed)
    return level + amp * np.sin(np.arange(n) / period) \
        + rng.normal(0, noise, n)


class TestArimaBankAgreement:
    def test_heterogeneous_bank_matches_scalars(self):
        cfgs = [dict(p=8, d=1), dict(p=4, d=2),
                dict(p=3, d=0, forgetting=0.98), dict(p=12, d=1)]
        scalars = [OnlineARIMA(**c) for c in cfgs]
        bank = ForecastBank(["arima"] * len(cfgs), params=cfgs, horizon=10)
        views = bank.views()
        streams = [sine_stream(400, seed=i) for i in range(len(cfgs))]
        for t in range(400):
            for i in range(len(cfgs)):
                scalars[i].update(streams[i][t])
                views[i].update(streams[i][t])
        for s, v in zip(scalars, views):
            np.testing.assert_allclose(v.forecast(10), s.forecast(10),
                                       rtol=1e-8, atol=1e-8)
            assert v.n_observed == s.n_observed == 400
            assert v.last() == pytest.approx(s.last(), rel=1e-12)
            assert v.residual_std() == pytest.approx(s.residual_std(),
                                                     rel=1e-6)

    def test_binned_forecast_decisions_match(self):
        s = OnlineARIMA(p=8, d=1)
        v = make_forecaster("arima", backend="bank", p=8, d=1)
        feed(100.0 + 5.0 * np.arange(200), s, v)
        assert binned_forecast(v, 10, 5) == pytest.approx(
            binned_forecast(s, 10, 5), rel=1e-9)

    def test_prewarmup_flat_forecast(self):
        s = OnlineARIMA(p=6, d=1)
        v = make_forecaster("arima", backend="bank", p=6, d=1)
        feed([42.0, 43.0], s, v)
        np.testing.assert_allclose(v.forecast(4), s.forecast(4))
        np.testing.assert_allclose(v.forecast(4), 43.0)

    def test_empty_forecast_is_zero(self):
        v = make_forecaster("arima", backend="bank")
        np.testing.assert_allclose(v.forecast(3), 0.0)

    def test_nan_updates_skipped_like_scalar(self):
        s = OnlineARIMA(p=4, d=1)
        v = make_forecaster("arima", backend="bank", p=4, d=1)
        feed([1.0, 2.0, np.nan, 3.0, 4.0, np.nan, 5.0, 6.0, 7.0,
              8.0, 9.0, 10.0], s, v)
        assert s.n_observed == v.n_observed == 10
        np.testing.assert_allclose(v.forecast(3), s.forecast(3), rtol=1e-10)

    def test_constant_stream_stays_constant(self):
        s = OnlineARIMA(p=4, d=1)
        v = make_forecaster("arima", backend="bank", p=4, d=1)
        feed(np.full(50, 7.5), s, v)
        np.testing.assert_allclose(s.forecast(5), 7.5)
        np.testing.assert_allclose(v.forecast(5), 7.5)

    def test_long_horizon_beyond_cache(self):
        s = OnlineARIMA(p=4, d=1)
        v = make_forecaster("arima", backend="bank", p=4, d=1, horizon=10)
        feed(sine_stream(120), s, v)
        np.testing.assert_allclose(v.forecast(25), s.forecast(25),
                                   rtol=1e-8, atol=1e-8)

    def test_interleaved_reads_and_updates(self):
        s = OnlineARIMA(p=4, d=1)
        v = make_forecaster("arima", backend="bank", p=4, d=1)
        for t in range(90):
            x = 30 + 3 * np.sin(t / 5)
            s.update(x)
            v.update(x)
            if t % 7 == 0:
                np.testing.assert_allclose(v.forecast(5), s.forecast(5),
                                           rtol=1e-9, atol=1e-9)

    def test_queue_overflow_flushes_in_order(self):
        # more staged updates than the queue holds between reads
        s = OnlineARIMA(p=4, d=1)
        v = make_forecaster("arima", backend="bank", p=4, d=1)
        feed(30.0 + 0.1 * np.arange(300), s, v)
        np.testing.assert_allclose(v.forecast(5), s.forecast(5), rtol=1e-9)


class TestDifferencingInversion:
    """Regression: d >= 2 used to add the same last level d times instead of
    cascading per-order tails, so quadratic trends diverged immediately."""

    def test_quadratic_trend_d2(self):
        m = OnlineARIMA(p=4, d=2)
        for t in range(400):
            m.update(0.5 * t ** 2 + 3.0 * t + 7.0)
        fc = m.forecast(10)
        true = np.array([0.5 * t ** 2 + 3.0 * t + 7.0
                         for t in range(400, 410)])
        np.testing.assert_allclose(fc, true, rtol=1e-5)

    def test_quadratic_trend_d2_bank(self):
        s = OnlineARIMA(p=4, d=2)
        v = make_forecaster("arima", backend="bank", p=4, d=2)
        feed([0.5 * t ** 2 + 3.0 * t + 7.0 for t in range(400)], s, v)
        np.testing.assert_allclose(v.forecast(10), s.forecast(10),
                                   rtol=1e-9)

    def test_linear_trend_d1_unchanged(self):
        m = OnlineARIMA(p=4, d=1)
        for t in range(300):
            m.update(10.0 + 2.0 * t)
        expected = 10.0 + 2.0 * (300 + np.arange(10))
        np.testing.assert_allclose(m.forecast(10), expected, rtol=0.02)


class TestBoundedMemory:
    """Ring buffers: state stays O(p + d + error windows) over 100k steps."""

    def test_arima_state_does_not_grow(self):
        m = OnlineARIMA(p=8, d=1)
        rng = np.random.default_rng(0)
        checkpoints = []
        for t in range(100_000):
            m.update(50.0 + np.sin(t / 10.0) + rng.normal(0, 0.1))
            if t in (1_000, 99_999):
                checkpoints.append((len(m._history), len(m._errors)))
        assert checkpoints[0] == checkpoints[1]
        assert len(m._history) == m.p + m.d + 1
        assert len(m._errors) == ERR_WINDOW
        assert m.n_observed == 100_000
        assert np.isfinite(m.forecast(5)).all()

    def test_detector_errors_do_not_grow(self):
        det = MetricDetector("m")
        rng = np.random.default_rng(1)
        for t in range(100_000):
            det.observe(1_000.0 + rng.normal(0, 20))
        assert len(det._errors) == DETECTOR_ERR_WINDOW
        assert len(det.model._history) == det.model.p + det.model.d + 1
        assert len(det.model._errors) == ERR_WINDOW

    def test_covariance_stays_finite_on_weak_excitation(self):
        # Regression: without per-step re-symmetrization, roundoff turns P
        # indefinite on weakly-excited streams (~6k samples at p=4, d=1)
        # and the recursion diverges to non-finite w.
        m = OnlineARIMA(p=4, d=1)
        rng = np.random.default_rng(1)
        for _ in range(25_000):
            m.update(1_000.0 + rng.normal(0, 20))
        assert np.isfinite(m._w).all()
        assert np.isfinite(m._P).all()
        np.testing.assert_array_equal(m._P, m._P.T)
        assert np.linalg.eigvalsh(m._P).min() > 0

    def test_detector_fires_after_long_benign_run(self):
        # Regression: a diverged model produced NaN predictions whose NaN
        # errors poisoned the MAD ring, silently disabling the detector.
        det = MetricDetector("m")
        rng = np.random.default_rng(1)
        for _ in range(12_000):
            det.observe(1_000.0 + rng.normal(0, 20))
        assert any(det.observe(0.0) for _ in range(30)), \
            "detector blind after a long healthy run"

    def test_bank_state_finite_on_weak_excitation(self):
        v = make_forecaster("arima", backend="bank", p=4, d=1)
        rng = np.random.default_rng(1)
        for _ in range(10_000):
            v.update(1_000.0 + rng.normal(0, 20))
        assert np.isfinite(v.forecast(5)).all()
        assert np.isfinite(np.asarray(v._fam.state.P)).all()

    def test_rollout_guard_bounds_unstable_forecasts(self):
        # Adversarial stream that can push the tracked AR coefficients
        # outside the stable region: the rollout must stay finite and
        # bounded instead of blowing up geometrically.
        rng = np.random.default_rng(2)
        s = OnlineARIMA(p=8, d=1)
        for t in range(5_000):
            s.update(50_000 + 5_000 * np.sin(t / 40) + rng.normal(0, 300))
        fc = s.forecast(20)
        assert np.isfinite(fc).all()
        assert np.max(np.abs(fc)) < 1e7


class TestHoltSeasonalFamilies:
    def test_holt_matches_scalar(self):
        kw = dict(alpha=0.4, beta=0.2, gamma=0.3, season=6)
        s = HoltWinters(**kw)
        v = make_forecaster("holt", backend="bank", **kw)
        feed([10 + 0.5 * t + 3 * np.sin(t / 3) for t in range(100)], s, v)
        np.testing.assert_allclose(v.forecast(8), s.forecast(8), rtol=1e-10)
        assert v.n_observed == s.n_observed
        assert v.residual_std() == pytest.approx(s.residual_std(), rel=1e-9)

    def test_holt_no_season_tracks_trend(self):
        s = HoltWinters(alpha=0.5, beta=0.2)
        v = make_forecaster("holt", backend="bank", alpha=0.5, beta=0.2)
        feed(10.0 + 2.0 * np.arange(300), s, v)
        np.testing.assert_allclose(s.forecast(3),
                                   10.0 + 2.0 * np.arange(300, 303),
                                   rtol=1e-6)
        np.testing.assert_allclose(v.forecast(3), s.forecast(3), rtol=1e-10)

    def test_seasonal_naive_matches_scalar(self):
        s = SeasonalNaive(season=5)
        v = make_forecaster("seasonal", backend="bank", season=5)
        feed([float(t % 5) * 3 + 1 for t in range(23)], s, v)
        np.testing.assert_allclose(v.forecast(12), s.forecast(12))

    def test_seasonal_naive_partial_season_is_flat(self):
        s = SeasonalNaive(season=8)
        v = make_forecaster("seasonal", backend="bank", season=8)
        feed([4.0, 5.0, 6.0], s, v)
        np.testing.assert_allclose(s.forecast(4), 6.0)
        np.testing.assert_allclose(v.forecast(4), s.forecast(4))

    def test_mixed_family_bank(self):
        kinds = ["arima", "holt", "seasonal", "arima"]
        params = [dict(p=4, d=1), dict(alpha=0.3, beta=0.1),
                  dict(season=4), dict(p=8, d=1)]
        scalars = [OnlineARIMA(p=4, d=1),
                   HoltWinters(alpha=0.3, beta=0.1),
                   SeasonalNaive(season=4), OnlineARIMA(p=8, d=1)]
        bank = ForecastBank(kinds, params=params, horizon=6)
        views = bank.views()
        stream = sine_stream(150, seed=3)
        for x in stream:
            for s, v in zip(scalars, views):
                s.update(x)
                v.update(x)
        for s, v in zip(scalars, views):
            np.testing.assert_allclose(v.forecast(6), s.forecast(6),
                                       rtol=1e-8, atol=1e-8)

    def test_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="unknown forecaster kind"):
            ForecastBank(["arma"])
        with pytest.raises(ValueError, match="unknown forecast backend"):
            make_forecaster("arima", backend="gpu")

    def test_kinds_registry(self):
        assert set(FORECASTER_KINDS) == {"arima", "holt", "seasonal"}


def outage_streams(seed=0):
    """(throughput, lag) streams: healthy -> outage -> recovered."""
    rng = np.random.default_rng(seed)
    thr = np.concatenate([50_000 + rng.normal(0, 200, 60),
                          np.zeros(20),
                          50_000 + rng.normal(0, 200, 40)])
    lag = np.concatenate([1_000 + rng.normal(0, 50, 60),
                          50_000 * np.arange(1, 21),
                          1_000 + rng.normal(0, 50, 40)])
    return thr, lag


class TestDetectorBank:
    def test_flags_match_scalar_through_outage(self):
        thr, lag = outage_streams()
        det_s = [MetricDetector("thr"), MetricDetector("lag")]
        det_b = DetectorBank(2)
        for a, b in zip(thr, lag):
            flags = det_b.observe(np.array([a, b]))
            assert bool(flags[0]) == det_s[0].observe(a)
            assert bool(flags[1]) == det_s[1].observe(b)

    def test_nan_gaps_skipped(self):
        det_s = MetricDetector("m")
        det_b = DetectorBank(1)
        rng = np.random.default_rng(4)
        for t in range(80):
            v = np.nan if t % 9 == 0 else 500.0 + rng.normal(0, 5)
            assert bool(det_b.observe(np.array([v]))[0]) == det_s.observe(v)

    def test_inactive_streams_not_updated(self):
        det_b = DetectorBank(2)
        rng = np.random.default_rng(5)
        for _ in range(30):
            det_b.observe(np.array([100.0 + rng.normal(), 0.0]),
                          active=np.array([True, False]))
        # stream 1 never saw a sample
        assert int(det_b._state.count[1]) == 0
        assert int(det_b._state.count[0]) == 30

    def test_recovery_tracker_bank_backend_matches_scalar(self):
        thr, lag = outage_streams(seed=7)
        tr_s = RecoveryTracker()
        tr_b = RecoveryTracker(detector_backend="bank")
        t = 0.0
        for a, b in zip(thr, lag):
            t += 5.0
            vals = {"throughput": a, "consumer_lag": b}
            assert tr_s.observe(t, vals) == tr_b.observe(t, vals)
        assert tr_s.episodes == tr_b.episodes
        assert tr_s.last_recovery_s == tr_b.last_recovery_s
        assert tr_s.last_recovery_s is not None

    def test_rejects_bad_shapes_and_backends(self):
        with pytest.raises(ValueError, match="expected 2 values"):
            DetectorBank(2).observe(np.zeros(3))
        with pytest.raises(ValueError, match="unknown detector backend"):
            RecoveryTracker(detector_backend="gpu")


class TestPallasKernel:
    def _random_spd(self, rng, B, k, dtype):
        a = rng.normal(0, 1, (B, k, k))
        return (a @ a.transpose(0, 2, 1) + np.eye(k)).astype(dtype)

    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_kernel_matches_ref(self, dtype):
        import contextlib

        import jax
        import jax.numpy as jnp

        from repro.kernels.ref import rls_rank1_update_ref
        from repro.kernels.rls_update import rls_rank1_update

        ctx = (jax.enable_x64() if dtype == np.float64
               else contextlib.nullcontext())
        with ctx:
            rng = np.random.default_rng(0)
            B, k = 13, 9                     # odd batch exercises padding
            P = self._random_spd(rng, B, k, dtype)
            phi = rng.normal(0, 1, (B, k)).astype(dtype)
            lam = np.full(B, 0.995, dtype)
            g1, p1 = rls_rank1_update(jnp.asarray(P), jnp.asarray(phi),
                                      jnp.asarray(lam), interpret=True)
            g2, p2 = rls_rank1_update_ref(jnp.asarray(P), jnp.asarray(phi),
                                          jnp.asarray(lam))
            tol = 1e-5 if dtype == np.float32 else 1e-12
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=tol, atol=tol)
            np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                                       rtol=tol, atol=tol)

    def test_bank_pallas_path_matches_scalar(self):
        s = OnlineARIMA(p=6, d=1)
        v = make_forecaster("arima", backend="bank", p=6, d=1,
                            use_pallas=True)
        feed(sine_stream(200, level=40.0, amp=5.0, period=9.0, noise=0.0),
             s, v)
        np.testing.assert_allclose(v.forecast(8), s.forecast(8), rtol=1e-9)
