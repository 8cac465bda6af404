"""Batched-vs-scalar modeling agreement tests.

The batched GPBank fit and the jitted EHVI path must reproduce the scalar
scipy/NumPy reference oracles: posterior mean/variance within tolerance,
identical Pareto subsets, and — the end-to-end guarantee the controller
relies on — the same selected profiling batch.
"""
import numpy as np
import pytest

from repro.core import (GP, GPBank, ModelBank, Segment, SegmentStore,
                        batched_posterior, ehvi_2d, ehvi_2d_batch,
                        pareto_front_2d, pareto_front_mask_2d,
                        select_profiling_batch)
from repro.core.demeter import FIT_MAX_ITER, FIT_RESTARTS
from repro.core.segments import LATENCY, METRICS, RECOVERY, USAGE


def _random_segments(rng, n_segments=6, dim=5):
    """Synthetic per-segment datasets shaped like controller training data."""
    datasets, seeds = [], []
    for i in range(n_segments):
        n = int(rng.integers(5, 20))
        x = rng.uniform(0, 1, (n, dim))
        level = 1.0 + 0.3 * i
        y = (level * (1.2 - x[:, 0]) + 0.4 * x[:, 1] ** 2
             + rng.normal(0, 0.05, n))
        datasets.append((x, y))
        seeds.append(i * 131)
    return datasets, seeds


class TestGPBankFit:
    @pytest.fixture(scope="class")
    def fitted(self):
        rng = np.random.default_rng(7)
        datasets, seeds = _random_segments(rng)
        scalars = [GP.fit(x, y, restarts=FIT_RESTARTS,
                          max_iter=FIT_MAX_ITER, seed=s)
                   for (x, y), s in zip(datasets, seeds)]
        bank = GPBank.fit(datasets, restarts=FIT_RESTARTS,
                          max_iter=FIT_MAX_ITER, seeds=seeds)
        return datasets, scalars, bank

    def test_posterior_agrees_with_scalar_oracle(self, fitted, rng):
        """Bank members' posterior mean/var match the scipy-fitted GPs."""
        datasets, scalars, bank = fitted
        xq = rng.uniform(0, 1, (128, 5))
        mu_b, var_b = bank.posterior(xq)
        for i, ((_, y), gp) in enumerate(zip(datasets, scalars)):
            mu, var = gp.posterior(xq)
            scale = np.std(y) or 1.0
            assert np.max(np.abs(mu - mu_b[i])) / scale < 0.05, \
                f"member {i} posterior mean drifted from the scipy fit"
            assert np.max(np.abs(var - var_b[i])) / scale ** 2 < 0.05, \
                f"member {i} posterior variance drifted from the scipy fit"

    def test_members_roundtrip_as_scalar_gps(self, fitted, rng):
        """A sliced-out member behaves like a plain GP (same API, finite)."""
        _, _, bank = fitted
        xq = rng.uniform(0, 1, (16, 5))
        mu_b, var_b = bank.posterior(xq)
        for i in range(bank.n_members):
            g = bank.member(i)
            mu, var = g.posterior(xq)
            np.testing.assert_allclose(mu, mu_b[i], rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(var, var_b[i], rtol=1e-3, atol=1e-5)
            s = g.loo_samples(8, np.random.default_rng(0))
            assert np.isfinite(s).all()

    def test_batched_posterior_matches_per_gp_loop(self, fitted, rng):
        _, scalars, _ = fitted
        xq = rng.uniform(0, 1, (64, 5))
        mu_b, var_b = batched_posterior(scalars, xq)
        for i, gp in enumerate(scalars):
            mu, var = gp.posterior(xq)
            np.testing.assert_allclose(mu, mu_b[i], rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(var, var_b[i], rtol=1e-3, atol=1e-5)

    def test_single_dataset_bank(self, rng):
        x = rng.uniform(0, 1, (12, 3))
        y = np.sin(3 * x[:, 0]) + x[:, 1]
        bank = GPBank.fit([(x, y)], seeds=[5])
        mu, var = bank.posterior(x)
        assert mu.shape == (1, 12)
        assert np.all(var > 0)
        assert np.abs(mu[0] - y).max() < 0.5

    def test_every_matmul_asks_for_full_float32(self):
        """The fit and posterior jaxprs carry HIGHEST on every dot_general
        (a TPU's default float32 matmul keeps ~3 digits)."""
        import jax
        import jax.numpy as jnp

        from repro.core.gp_bank import (_fit_packed, _posterior_packed,
                                        _rgpe_reads_packed)

        def dots(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "dot_general":
                    yield eqn.params["precision"]
                for p in eqn.params.values():
                    for sub in (p if isinstance(p, (list, tuple)) else [p]):
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            yield from dots(inner)

        B, n, d = 2, 8, 5
        f = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
        fit = jax.make_jaxpr(lambda *a: _fit_packed(*a, max_iter=3))(
            f(B, n, d), f(B, n), f(B, n), f(B, 2, d + 2))
        post = jax.make_jaxpr(_posterior_packed)(
            f(B, n, d), f(B, n), f(B, d + 2), f(B, n, n), f(B, n), f(4, d))
        reads = jax.make_jaxpr(_rgpe_reads_packed)(
            f(B, n, d), f(B, n), f(B, d + 2), f(B, n, n), f(B, n), f(n, d),
            f(n), f(d + 2))
        highest = (jax.lax.Precision.HIGHEST,) * 2
        for name, jaxpr in (("fit", fit), ("posterior", post),
                            ("rgpe_reads", reads)):
            found = list(dots(jaxpr.jaxpr))
            assert found, name
            assert all(p == highest for p in found), (name, found)

    def test_rgpe_reads_pad_query_rows(self, make_gp):
        """A build's reads at the target's points, padded to a power of
        two, equal an unpadded batched read of the same rows; target sizes
        in one bucket share one program, counted in ``jit_cache_size``."""
        from repro.core.gp_bank import (_posterior_packed, _rgpe_reads_packed,
                                        jit_cache_size, rgpe_reads)
        rng = np.random.default_rng(3)
        dim = 7                      # a width no other test compiles
        bases = [make_gp(rng, n, dim) for n in (6, 11, 20)]
        cache0 = jit_cache_size()
        own0 = _rgpe_reads_packed._cache_size()
        post0 = _posterior_packed._cache_size()
        for n in (9, 13):            # both padded to 16 rows
            target = make_gp(rng, n, dim, shift=2.0)
            reads, _ = rgpe_reads(bases, target)
            mu_b, var_b = batched_posterior(bases, target.x)
            for (mu, var), m_b, v_b in zip(reads, mu_b, var_b):
                assert mu.shape == var.shape == (n,)
                np.testing.assert_allclose(mu, m_b, rtol=1e-5, atol=1e-6)
                np.testing.assert_allclose(var, v_b, rtol=1e-5, atol=1e-9)
        assert _rgpe_reads_packed._cache_size() - own0 == 1
        # plus one unpadded batched program for each of the two sizes
        assert _posterior_packed._cache_size() - post0 == 2
        assert jit_cache_size() - cache0 == 3
        with pytest.raises(ValueError, match="at least one"):
            rgpe_reads([], target)

    def test_rejects_empty_and_mixed_dims(self, rng):
        with pytest.raises(ValueError, match="at least one"):
            GPBank.fit([])
        a = (rng.uniform(0, 1, (5, 2)), rng.normal(0, 1, 5))
        b = (rng.uniform(0, 1, (5, 3)), rng.normal(0, 1, 5))
        with pytest.raises(ValueError, match="dim"):
            GPBank.fit([a, b])


class TestBatchedEHVI:
    def test_matches_numpy_oracle_across_random_fronts(self, rng):
        B, n = 6, 32
        mu = rng.uniform(0, 5, (B, n, 2))
        var = rng.uniform(0.01, 1.0, (B, n, 2))
        fronts = [rng.uniform(0, 4, (int(rng.integers(0, 10)), 2))
                  for _ in range(B)]
        refs = np.full((B, 2), 5.0)
        out = ehvi_2d_batch(mu, var, fronts, refs)
        for i in range(B):
            want = ehvi_2d(mu[i], var[i], fronts[i], (5.0, 5.0))
            np.testing.assert_allclose(out[i], want, rtol=1e-3, atol=1e-5)

    def test_empty_front_row(self, rng):
        mu = rng.uniform(0, 2, (1, 8, 2))
        var = np.full((1, 8, 2), 0.25)
        out = ehvi_2d_batch(mu, var, [np.zeros((0, 2))],
                            np.array([[3.0, 3.0]]))
        want = ehvi_2d(mu[0], var[0], np.zeros((0, 2)), (3.0, 3.0))
        np.testing.assert_allclose(out[0], want, rtol=1e-3, atol=1e-5)

    def test_pareto_mask_equals_front(self, rng):
        for _ in range(25):
            k = int(rng.integers(1, 16))
            pts = rng.uniform(0, 4, (k, 2))
            mask = pareto_front_mask_2d(pts[None])[0]
            got = np.sort(pts[mask], axis=0)
            want = np.sort(pareto_front_2d(pts), axis=0)
            np.testing.assert_allclose(got, want)

    def test_pareto_mask_respects_validity(self, rng):
        pts = np.array([[[1.0, 1.0], [0.1, 0.1], [2.0, 0.5]]])
        valid = np.array([[True, False, True]])
        mask = pareto_front_mask_2d(pts, valid)
        # the dominated-but-invalid point must not be selected nor shadow
        assert not mask[0, 1]
        assert mask[0, 0]


class TestSelectionAgreement:
    """The controller-facing guarantee: same profiling batch either way."""

    def _posteriors(self, gps_u, gps_l):
        def post(x):
            mu_u, var_u = gps_u.posterior(x)
            mu_l, var_l = gps_l.posterior(x)
            return (np.stack([mu_u, mu_l], 1), np.stack([var_u, var_l], 1))
        return post

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_same_profiling_batch_selected(self, seed):
        rng = np.random.default_rng(seed)
        n = 15
        x = rng.uniform(0, 1, (n, 4))
        usage = 1.5 - x[:, 0] + 0.2 * x[:, 1] + rng.normal(0, 0.03, n)
        lat = 0.5 + x[:, 0] ** 2 + rng.normal(0, 0.03, n)

        su = GP.fit(x, usage, restarts=FIT_RESTARTS,
                    max_iter=FIT_MAX_ITER, seed=3)
        sl = GP.fit(x, lat, restarts=FIT_RESTARTS,
                    max_iter=FIT_MAX_ITER, seed=4)
        bank = GPBank.fit([(x, usage), (x, lat)], restarts=FIT_RESTARTS,
                          max_iter=FIT_MAX_ITER, seeds=[3, 4])
        bu, bl = bank.member(0), bank.member(1)

        cand = rng.uniform(0, 1, (96, 4))
        front = np.stack([usage, lat], 1)
        ref = (float(usage.max()) * 1.2, float(lat.max()) * 1.2)

        picked_scalar = select_profiling_batch(
            cand, self._posteriors(su, sl), None, front, ref, q=3,
            backend="numpy")
        picked_bank = select_profiling_batch(
            cand, self._posteriors(bu, bl), None, front, ref, q=3,
            backend="jax")
        assert picked_scalar == picked_bank, \
            "batched fit + jitted EHVI changed the profiling batch"


class TestModelBankBackends:
    def _store_with_data(self, rng, n_obs=8):
        store = SegmentStore(10_000.0)
        for i in range(n_obs):
            x = rng.uniform(0, 1, 3)
            metrics = {USAGE: float(1.5 - x[0] + rng.normal(0, 0.02)),
                       LATENCY: float(0.5 + x[0] ** 2),
                       RECOVERY: float(60.0 + 100 * x[1])}
            store.record({"a": i}, x, 15_000.0, metrics)
        return store

    def test_bank_and_scalar_backends_agree(self, rng):
        store = self._store_with_data(rng)
        seg = store.segment_for(15_000.0)
        mb_bank = ModelBank(store, fit_backend="bank")
        mb_scalar = ModelBank(store, fit_backend="scalar")
        xq = rng.uniform(0, 1, (32, 3))
        for metric in METRICS:
            gb = mb_bank.gp(seg, metric)
            gs = mb_scalar.gp(seg, metric)
            assert (gb is None) == (gs is None)
            if gb is None:
                continue
            mu_b, _ = gb.posterior(xq)
            mu_s, _ = gs.posterior(xq)
            scale = np.std(seg.data(metric)[1]) or 1.0
            assert np.max(np.abs(mu_b - mu_s)) / scale < 0.05

    def test_refresh_fits_everything_stale(self, rng):
        store = self._store_with_data(rng)
        mb = ModelBank(store)
        n = mb.refresh()
        assert n == len(METRICS)
        assert mb.refresh() == 0              # now fresh
        seg = store.segment_for(15_000.0)
        assert mb.gp(seg, USAGE) is not None  # cache hit, no refit
        assert mb.n_fits == 0                 # all fits were batched

    def test_batch_refresh_spans_banks(self, rng):
        stores = [self._store_with_data(rng) for _ in range(3)]
        banks = [ModelBank(s) for s in stores]
        n, wall = ModelBank.batch_refresh(banks)
        assert n == 3 * len(METRICS)
        assert wall >= 0.0
        n2, _ = ModelBank.batch_refresh(banks)
        assert n2 == 0

    def test_version_staleness(self, rng):
        store = self._store_with_data(rng, n_obs=12)
        seg = store.segment_for(15_000.0)
        mb = ModelBank(store)
        g1 = mb.gp(seg, USAGE)
        assert mb.gp(seg, USAGE) is g1        # cached by version
        v = seg.version
        x = rng.uniform(0, 1, 3)
        store.record({"a": 99}, x, 15_000.0, {USAGE: 0.7})
        assert seg.version == v + 1           # 12 -> 13 is < 10% growth
        assert mb.gp(seg, USAGE) is g1        # fresh enough, no refit

    def test_rejects_unknown_backend(self):
        with pytest.raises(ValueError, match="unknown fit backend"):
            ModelBank(SegmentStore(10_000.0), fit_backend="torch")
