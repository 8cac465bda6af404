"""Unit tests for the Demeter modeling stack (GP, ARIMA, RGPE, latency)."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property-based tests need the optional dep
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core import (GP, LatencyConstraint, OnlineARIMA, RGPEnsemble,
                        binned_forecast, build_rgpe)
from repro.core.gp_bank import rgpe_reads
from repro.core.rgpe import _ranking_loss


def _eager_rgpe_weights(target, bases, n_samples=256, seed=0):
    """RGPE weights from the scalar oracle's reads: GP.sample per base
    model, then GP.loo_samples, from one generator."""
    ty = np.asarray(target.train_targets, np.float64)
    rng = np.random.default_rng(seed)
    losses = [_ranking_loss(g.sample(target.x, n_samples, rng), ty)
              for g in bases]
    target_loss = _ranking_loss(target.loo_samples(n_samples, rng), ty)
    loss = np.stack(losses + [target_loss])
    loss[:-1][loss[:-1] > np.percentile(target_loss, 95.0)] = np.inf
    w = np.zeros(len(loss))
    mins = loss.min(axis=0)
    for col in range(loss.shape[1]):
        winners = np.flatnonzero(loss[:, col] == mins[col])
        w[winners] += 1.0 / len(winners)
    w /= loss.shape[1]
    w = np.where(w > 1e-3, w, 0.0)
    return w / w.sum()


class TestGP:
    def test_fit_recovers_smooth_function(self, rng):
        x = rng.uniform(0, 1, (40, 2))
        y = np.sin(3 * x[:, 0]) + x[:, 1] ** 2
        gp = GP.fit(x, y)
        xq = rng.uniform(0.05, 0.95, (100, 2))
        mu, var = gp.posterior(xq)
        true = np.sin(3 * xq[:, 0]) + xq[:, 1] ** 2
        assert np.sqrt(np.mean((mu - true) ** 2)) < 0.1
        assert np.all(var > 0)

    def test_posterior_interpolates_training_points(self, rng):
        x = rng.uniform(0, 1, (20, 3))
        y = rng.normal(0, 1, 20)
        gp = GP.fit(x, y)
        mu, var = gp.posterior(x)
        # noise is learned, so interpolation is approximate but tight
        assert np.abs(mu - y).max() < 0.5
        # posterior variance at data < prior variance away from data
        far = np.full((1, 3), 2.0)
        _, var_far = gp.posterior(far)
        assert var.mean() < var_far[0]

    def test_train_targets_roundtrip(self, rng):
        x = rng.uniform(0, 1, (15, 2))
        y = rng.normal(3.0, 2.0, 15)
        gp = GP.fit(x, y)
        np.testing.assert_allclose(gp.train_targets, y, atol=1e-2)

    def test_loo_samples_shape_and_finite(self, rng):
        x = rng.uniform(0, 1, (12, 2))
        y = rng.normal(0, 1, 12)
        gp = GP.fit(x, y)
        s = gp.loo_samples(32, rng)
        assert s.shape == (32, 12)
        assert np.isfinite(s).all()


class TestOnlineARIMA:
    def test_tracks_linear_trend(self):
        m = OnlineARIMA(p=4, d=1)
        for t in range(300):
            m.update(10.0 + 2.0 * t)
        fc = m.forecast(10)
        expected = 10.0 + 2.0 * (300 + np.arange(10))
        np.testing.assert_allclose(fc, expected, rtol=0.02)

    def test_tracks_seasonal_signal(self):
        m = OnlineARIMA(p=12, d=1)
        t = np.arange(800)
        sig = 100 + 20 * np.sin(2 * np.pi * t / 40)
        for v in sig:
            m.update(v)
        fc = m.forecast(40)
        true = 100 + 20 * np.sin(2 * np.pi * (800 + np.arange(40)) / 40)
        assert np.mean(np.abs(fc - true)) < 2.0

    def test_binned_forecast_picks_max_bin(self):
        m = OnlineARIMA(p=4, d=1)
        for t in range(200):
            m.update(100.0 + 5.0 * t)   # rising -> furthest bin largest
        pred = binned_forecast(m, horizon=20, bins=4)
        fc = m.forecast(20)
        assert pred == pytest.approx(max(np.array_split(fc, 4)[i].mean()
                                         for i in range(4)))
        assert pred > m.last()

    def test_prewarmup_is_flat(self):
        m = OnlineARIMA(p=8, d=1)
        m.update(50.0)
        np.testing.assert_allclose(m.forecast(5), 50.0)


class TestRGPE:
    def test_informative_base_model_gets_weight(self, rng):
        # Base task == target task (shifted): ranking is shift-invariant,
        # so the base model should carry substantial weight.
        f = lambda x: np.sin(3 * x[:, 0]) + x[:, 1]
        bx = rng.uniform(0, 1, (40, 2))
        base = GP.fit(bx, f(bx))
        tx = rng.uniform(0, 1, (6, 2))
        ty = f(tx) + 5.0
        target = GP.fit(tx, ty)
        ens = build_rgpe(target, tx, ty, [base])
        assert ens.weights[0] > 0.3

    def test_uninformative_base_model_diluted(self, rng):
        f = lambda x: np.sin(3 * x[:, 0])
        bx = rng.uniform(0, 1, (40, 2))
        base = GP.fit(bx, rng.normal(0, 1, 40))     # pure noise task
        tx = rng.uniform(0, 1, (10, 2))
        ty = f(tx)
        target = GP.fit(tx, ty)
        ens = build_rgpe(target, tx, ty, [base])
        assert ens.weights[-1] > ens.weights[0]

    def test_cold_start_uniform(self, rng):
        bx = rng.uniform(0, 1, (20, 2))
        base = GP.fit(bx, rng.normal(0, 1, 20))
        ens = build_rgpe(None, np.zeros((0, 2)), np.zeros(0), [base])
        assert ens.n_members == 1
        mu, var = ens.posterior(rng.uniform(0, 1, (5, 2)))
        assert np.isfinite(mu).all() and (var > 0).all()

    def test_no_models_returns_none(self):
        assert build_rgpe(None, np.zeros((0, 2)), np.zeros(0), []) is None

    def test_paper_variance_combination(self, rng):
        x = rng.uniform(0, 1, (10, 2))
        y = rng.normal(0, 1, 10)
        g1, g2 = GP.fit(x, y, seed=0), GP.fit(x, y, seed=1)
        ens = RGPEnsemble([g1, g2], np.array([0.5, 0.5]))
        xq = rng.uniform(0, 1, (4, 2))
        mu, var = ens.posterior(xq)
        m1, v1 = g1.posterior(xq)
        m2, v2 = g2.posterior(xq)
        # members evaluate through the batched float32 kernel; allow f32 noise
        np.testing.assert_allclose(mu, 0.5 * m1 + 0.5 * m2, rtol=1e-5)
        np.testing.assert_allclose(var, 0.25 * v1 + 0.25 * v2, rtol=1e-5)


class TestPackedRGPE:
    @pytest.mark.parametrize("seed, dim, n_bases, n_target", [
        (0, 2, 1, 3), (1, 5, 2, 4), (2, 2, 3, 5), (3, 5, 1, 7),
        (4, 2, 2, 8), (5, 5, 3, 9), (6, 2, 1, 11), (7, 5, 2, 12),
        (8, 2, 3, 6), (9, 5, 3, 10)])
    def test_build_weights_equal_eager_assembly(self, make_gp, seed, dim,
                                                n_bases, n_target):
        rng = np.random.default_rng(seed)
        bases = [make_gp(rng, int(rng.choice([6, 20])), dim, shift=i)
                 for i in range(n_bases)]
        target = make_gp(rng, n_target, dim, shift=5.0)
        ens = build_rgpe(target, target.x, target.train_targets, bases,
                         seed=seed)
        want = _eager_rgpe_weights(target, bases, seed=seed)
        np.testing.assert_allclose(ens.weights, want, rtol=0, atol=1e-12)

    @pytest.mark.parametrize("n", [5, 8])     # padded to 8, and unpadded
    def test_packed_loo_matches_closed_form(self, make_gp, n):
        rng = np.random.default_rng(n)
        base, target = make_gp(rng, 12, 3), make_gp(rng, n, 3, shift=2.0)
        _, (mu, var) = rgpe_reads([base], target)
        assert mu.shape == var.shape == (n,)
        # the oracle's draws from the same generator state
        got = target.loo_draws(mu, var, 64, np.random.default_rng(1))
        want = target.loo_samples(64, np.random.default_rng(1))
        np.testing.assert_allclose(got, want, rtol=1e-5)
        # the closed form in float64 from the stored factor
        ys = (target.chol.astype(np.float64) @ target.chol.T) @ target.alpha
        kinv = np.linalg.inv(target.chol.astype(np.float64) @ target.chol.T)
        d = np.diag(kinv)
        np.testing.assert_allclose(var, 1.0 / d, rtol=1e-3)
        np.testing.assert_allclose(mu, ys - target.alpha / d, rtol=1e-3,
                                   atol=1e-3)

    def test_one_member_read_matches_scalar_posterior(self, make_gp, rng):
        g = make_gp(rng, 10, 2, shift=3.0)
        xq = rng.uniform(0, 1, (7, 2))
        mu, var = RGPEnsemble([g, make_gp(rng, 6, 2)],
                              np.array([0.7, 0.0])).posterior(xq)
        m, v = g.posterior(xq)
        np.testing.assert_allclose(mu, 0.7 * m, rtol=1e-5)
        np.testing.assert_allclose(var, 0.49 * v, rtol=1e-5)

    def test_reads_count_packed_not_single(self, make_gp, rng):
        bases = [make_gp(rng, 9, 2), make_gp(rng, 14, 2)]
        target = make_gp(rng, 6, 2, shift=1.0)
        obs.disable()
        obs.reset()
        obs.enable()
        try:
            ens = build_rgpe(target, target.x, target.train_targets, bases)
            ens.posterior(rng.uniform(0, 1, (3, 2)))
            RGPEnsemble([target], np.array([1.0])).posterior(target.x)
            counters = obs.snapshot()["counters"]
        finally:
            obs.disable()
            obs.reset()
        assert counters["gp.packed_reads"] == 3
        assert counters["gp.single_reads"] == 0


class TestLatencyConstraint:
    def test_boundary_is_twice_p1(self):
        lc = LatencyConstraint()
        for v in np.linspace(1.0, 1.1, 50):
            lc.observe(v)
        assert lc.constraint() == pytest.approx(2 * np.percentile(
            np.linspace(1.0, 1.1, 50), 1.0))
        assert lc.is_normal(1.5)
        assert not lc.is_normal(3.0)

    def test_transform_range(self):
        lc = LatencyConstraint()
        for v in np.linspace(1.0, 2.0, 100):
            lc.observe(v)
        ts = [lc.transform(v) for v in (1.0, 2.0, 5.0, 100.0)]
        assert all(0.0 <= t < 1.0 for t in ts)
        assert ts == sorted(ts)            # monotone

    def test_prewarmup_permissive(self):
        lc = LatencyConstraint()
        assert lc.constraint() is None
        assert lc.is_normal(1e9)


@given(st.lists(st.floats(0.1, 1e4), min_size=8, max_size=64))
@settings(max_examples=25, deadline=None)
def test_latency_transform_always_bounded(values):
    lc = LatencyConstraint()
    for v in values:
        lc.observe(v)
    for v in values:
        assert 0.0 <= lc.transform(v) <= 1.0
