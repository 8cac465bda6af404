"""Property tests pinning ForecastBank/DetectorBank to the scalar oracles.

Random AR orders, differencing orders, forgetting factors and NaN/constant
streams must produce the same updates, rollouts and anomaly flags on both
backends. Needs the optional ``hypothesis`` dependency (the ``test``
extra); deterministic agreement tests live in ``test_forecast_bank.py``.

Agreement tolerances are loose-ish (1e-5 relative) because the RLS
recursion is numerically chaotic over long horizons — see
``docs/FORECAST.md``. Where the regressor sits weakly excited (a flat run,
or an undifferenced level that dwarfs its noise) the covariance winds up
towards the trace cap, a jump then makes ``φᵀPφ/λ`` reach ~1e11, and the
downdate keeps ~5 digits: there the float64 oracle is itself more than
1e-5 away from the same recursion run in extended precision
(:func:`arima_forecast_extended`, witnessed by
``test_float64_oracle_drifts_on_flat_then_spike``), so no second float64
path can be held to 1e-5 against it. The ARIMA agreement property
therefore measures that drift on every example and keeps those where the
oracle stays within :data:`ORACLE_DRIFT_MAX` of the extended-precision run.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property-based tests need the optional dep
from hypothesis import assume, given, settings, strategies as st

from repro.core import (DetectorBank, HoltWinters, MetricDetector,
                        OnlineARIMA, SeasonalNaive, binned_forecast,
                        make_forecaster)
from repro.core.forecast import P_TRACE_CAP, ROLLOUT_DIFF_CAP

#: the extended type must carry more digits than float64 for the
#: drift measurement to mean anything (x86 long double: 64-bit mantissa)
EXTENDED = np.longdouble
HAS_EXTENDED = np.finfo(EXTENDED).eps < np.finfo(np.float64).eps
#: relative drift of the float64 oracle from the extended-precision run
#: below which an example counts as float64-representable
ORACLE_DRIFT_MAX = 1e-7

finite_vals = st.floats(min_value=1.0, max_value=1e5, allow_nan=False)
stream = st.lists(st.one_of(finite_vals, st.just(float("nan"))),
                  min_size=30, max_size=120)


@st.composite
def level_shifts(draw):
    """Piecewise-stationary telemetry like the flash and regime traces: a
    level in [1, 1e5] that jumps up or down by 2-10x up to three times,
    1-10 % Gaussian noise (seeded from the example) and ~10 % NaN gaps."""
    level = draw(finite_vals)
    levels = [level] * draw(st.integers(20, 40))
    for factor, up, n in draw(st.lists(st.tuples(
            st.floats(2.0, 10.0), st.booleans(), st.integers(8, 40)),
            max_size=3)):
        level = level * factor if up else level / factor
        levels += [level] * n
    rng = np.random.default_rng(draw(st.integers(0, 2 ** 32 - 1)))
    noise = draw(st.floats(0.01, 0.1))
    vals = np.asarray(levels) * (1.0 + noise * rng.standard_normal(
        len(levels)))
    vals[rng.random(len(levels)) < 0.1] = np.nan
    return vals.tolist()


def arima_forecast_extended(values, p, d, lam, steps, ridge=10.0):
    """``OnlineARIMA(p, d, lam, ridge)`` fed ``values`` and rolled out
    ``steps`` ahead, with every operation in :data:`EXTENDED` precision:
    the same recursion, guards and caps, rounded ~2048x finer."""
    X = EXTENDED
    hist, n_seen, w, P = [], 0, None, None
    for v in values:
        if not np.isfinite(v):
            continue
        hist = (hist + [X(v)])[-(p + d + 1):]
        n_seen += 1
        if n_seen < p + d + 1:
            continue
        diffed = np.diff(np.asarray(hist, X), n=d)
        phi = np.concatenate([diffed[:-1][-p:][::-1], [X(1)]])
        if w is None:
            w, P = np.zeros(p + 1, X), np.eye(p + 1, dtype=X) * X(ridge)
        Pphi = P @ phi
        gain = Pphi / (X(lam) + phi @ Pphi)
        w = w + gain * (diffed[-1] - w @ phi)
        P = (P - np.outer(gain, Pphi)) / X(lam)
        P = (P + P.T) / X(2)
        cap = X(ridge * (p + 1) * P_TRACE_CAP)
        if np.trace(P) > cap:
            P *= cap / np.trace(P)
        if not (np.isfinite(w).all() and np.isfinite(P).all()):
            w, P = np.zeros(p + 1, X), np.eye(p + 1, dtype=X) * X(ridge)
    if w is None:
        return np.full(steps, hist[-1] if hist else X(0), X)
    series = np.asarray(hist, X)
    diffed = list(np.diff(series, n=d))
    tails = [np.diff(series, n=j)[-1] for j in range(d)]
    lim = X(ROLLOUT_DIFF_CAP) * max(X(1), np.max(np.abs(diffed[-p:])))
    out = []
    for _ in range(steps):
        phi = np.concatenate([np.asarray(diffed[-p:], X)[::-1], [X(1)]])
        dnext = np.clip(w @ phi, -lim, lim)
        diffed = (diffed + [dnext])[-p:]
        v = dnext
        for j in range(d - 1, -1, -1):
            v = v + tails[j]
            tails[j] = v
        out.append(v)
    return np.asarray(out, X)


def drift(a, exact) -> float:
    """max |a - exact| relative to 1 + max |exact|."""
    return float(np.max(np.abs(a - exact)) / (1 + np.max(np.abs(exact))))


def feed(values, *models):
    for v in values:
        for m in models:
            m.update(v)


@given(p=st.integers(1, 10), d=st.integers(0, 2),
       lam=st.floats(0.9, 0.999), values=st.one_of(stream, level_shifts()))
@settings(max_examples=15, deadline=None)
def test_arima_bank_matches_scalar(p, d, lam, values):
    s = OnlineARIMA(p=p, d=d, forgetting=lam)
    v = make_forecaster("arima", backend="bank", p=p, d=d, forgetting=lam)
    feed(values, s, v)
    a, b = s.forecast(7), v.forecast(7)
    exact = arima_forecast_extended(values, p, d, lam, 7)
    # Only where float64 itself can carry the answer (module docstring);
    # the bank is then held to the oracle and to the extended run alike.
    assume(drift(a, exact) < ORACLE_DRIFT_MAX)
    assert drift(b, exact) < 1e-5
    scale = 1.0 + np.max(np.abs(a))
    np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5 * scale)
    assert s.n_observed == v.n_observed
    assert binned_forecast(v, 7, 3) == pytest.approx(
        binned_forecast(s, 7, 3), rel=1e-4, abs=1e-5 * scale)


def test_float64_oracle_drifts_on_flat_then_spike():
    # The shrunk counterexample of the agreement property on arbitrary
    # streams: a flat run at 1.0 with gaps, one 2e4 spike, flat again.
    # The float64 oracle lands ~5e-5 from the extended-precision run, so
    # 1e-5 agreement between two float64 paths is not defined here.
    if not HAS_EXTENDED:
        pytest.skip("long double is float64 here")
    nan = float("nan")
    values = [nan, 1, 1, 1, nan, nan, 1, 1, 1, 1, nan, 1, 1, 20240.0, 1,
              1, 1, 1, nan, 1, nan, 1, 1, 1, 1, nan, nan, nan, 1, nan]
    s = OnlineARIMA(p=1, d=2, forgetting=0.9375)
    feed([float(x) for x in values], s)
    exact = arima_forecast_extended(values, 1, 2, 0.9375, 7)
    assert drift(s.forecast(7), exact) > 1e-5
    # ...while on a noisy stream the two precisions agree to float64
    # roundoff: the extended run is the same recursion, not another one.
    noisy = list(100.0 * (1.0 + 0.1 * np.random.default_rng(0)
                          .standard_normal(80)))
    s = OnlineARIMA(p=3, d=1, forgetting=0.99)
    feed(noisy, s)
    assert drift(s.forecast(7),
                 arima_forecast_extended(noisy, 3, 1, 0.99, 7)) < 1e-12


@given(p=st.integers(1, 10), d=st.integers(0, 2),
       lam=st.floats(0.9, 0.999), values=stream)
@settings(max_examples=15, deadline=None)
def test_arima_bank_bookkeeping_on_adversarial_streams(p, d, lam, values):
    # Every stream, including those where float64 itself drifts (module
    # docstring): both paths count the same observations and stay finite.
    s = OnlineARIMA(p=p, d=d, forgetting=lam)
    v = make_forecaster("arima", backend="bank", p=p, d=d, forgetting=lam)
    feed(values, s, v)
    assert s.n_observed == v.n_observed
    assert np.isfinite(s.forecast(7)).all() and np.isfinite(v.forecast(7)).all()


@given(const=finite_vals, n=st.integers(10, 60),
       p=st.integers(1, 8), d=st.integers(0, 2))
@settings(max_examples=15, deadline=None)
def test_constant_stream_agreement(const, n, p, d):
    s = OnlineARIMA(p=p, d=d)
    v = make_forecaster("arima", backend="bank", p=p, d=d)
    feed([const] * n, s, v)
    a, b = s.forecast(5), v.forecast(5)
    np.testing.assert_allclose(b, a, rtol=1e-7, atol=1e-7 * (1 + abs(const)))


@given(alpha=st.floats(0.05, 0.95), beta=st.floats(0.01, 0.9),
       gamma=st.floats(0.01, 0.9), season=st.integers(0, 8), values=stream)
@settings(max_examples=15, deadline=None)
def test_holt_bank_matches_scalar(alpha, beta, gamma, season, values):
    kw = dict(alpha=alpha, beta=beta, gamma=gamma, season=season)
    s = HoltWinters(**kw)
    v = make_forecaster("holt", backend="bank", **kw)
    feed(values, s, v)
    a, b = s.forecast(6), v.forecast(6)
    np.testing.assert_allclose(b, a, rtol=1e-9,
                               atol=1e-9 * (1.0 + np.max(np.abs(a))))
    assert s.n_observed == v.n_observed


@given(season=st.integers(1, 10), values=stream)
@settings(max_examples=15, deadline=None)
def test_seasonal_naive_bank_matches_scalar(season, values):
    s = SeasonalNaive(season=season)
    v = make_forecaster("seasonal", backend="bank", season=season)
    feed(values, s, v)
    np.testing.assert_allclose(v.forecast(2 * season + 1),
                               s.forecast(2 * season + 1))


@given(base=st.floats(100.0, 1e4), noise=st.floats(0.001, 0.05),
       outage_at=st.integers(25, 50), seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_detector_flags_match_scalar(base, noise, outage_at, seed):
    rng = np.random.default_rng(seed)
    healthy = base * (1.0 + rng.normal(0, noise, 70))
    values = np.concatenate([healthy[:outage_at], np.zeros(10),
                             healthy[outage_at:]])
    det_s = MetricDetector("m")
    det_b = DetectorBank(1)
    for t, v in enumerate(values):
        assert bool(det_b.observe(np.array([v]))[0]) == det_s.observe(v), \
            f"flag diverged at step {t}"
