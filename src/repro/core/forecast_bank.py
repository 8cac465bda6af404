"""Batched online forecasting + anomaly detection (paper §2.2–2.3).

After the batched simulator (PR 1) and the batched GP/MOBO bank (PR 2), the
workload forecasters and anomaly detectors were the last scalar, per-sample
components in the sweep hot path: every scenario carried its own Python
forecaster objects updated sample-by-sample. This module packs all
(scenario × metric-stream) online forecaster states into stacked arrays and
advances **every** stream with one jitted update per sweep tick:

* :class:`ForecastBank` — the batched forecaster zoo. Streams are grouped
  by family (``arima`` / ``holt`` / ``seasonal``, mirroring the scalar zoo
  in :mod:`repro.core.forecast`); each family advances through a single
  vmapped update per flush. For the ARIMA family that is a batched
  rank-1 RLS step — weights ``w[B, k]``, covariances ``P[B, k, k]``,
  ring-buffered differenced-lag windows and per-order differencing tails —
  optionally lowered to the Pallas kernel in
  :mod:`repro.kernels.rls_update`; multistep rollout runs as a
  ``lax.scan``. Updates are *staged* per stream into write-behind queues
  and :meth:`ForecastBank.flush` replays every queued tick of every stream
  through one ``lax.scan`` dispatch when the next forecast is read, so the
  whole grid pays a single XLA call per read epoch — batched across
  streams *and* ticks.
* :class:`DetectorBank` — the §2.3 one-step-error anomaly detectors,
  batched: one jitted call per sample advances every stream's ARIMA
  predictor, compares the absolute one-step error against a streaming
  median + k·MAD threshold over a fixed-size healthy-error ring (no
  unbounded lists), and coasts anomalous streams on their own prediction.

Numerics: bank state is float64 (dispatches run under
``jax.enable_x64``), so every family agrees with its scalar
NumPy oracle to reduction-order rounding (~1e-12 relative) and the
agreement — forecasts, binned-forecast decisions, anomaly flags — is pinned
in ``tests/test_forecast_bank.py``. Heterogeneous AR orders / differencing
orders share one padded layout: inactive lag dimensions are masked out of
the regression vector and their covariance block stays pinned at its
``ridge·I`` initialization, so a member behaves exactly like an unpadded
stream.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .anomaly import DETECTOR_ERR_WINDOW
from .forecast import (ERR_WINDOW, FORECASTER_DEFAULTS, FORECASTER_KINDS,
                       P_TRACE_CAP, ROLLOUT_DIFF_CAP, make_scalar_forecaster)
from .gp_bank import bucket_pow2
from .registry import FORECAST_BACKENDS


# ---------------------------------------------------------------------------
# ARIMA family: AR(p) on the d-differenced series, RLS-tracked
# ---------------------------------------------------------------------------

class _ArimaState(NamedTuple):
    w: jnp.ndarray        # (B, k)    AR coefficients + bias (k = p_max + 1)
    P: jnp.ndarray        # (B, k, k) RLS inverse covariance
    lags: jnp.ndarray     # (B, p_max) differenced lags, newest first
    tails: jnp.ndarray    # (B, d_max) last value of the j-times-diffed series
    count: jnp.ndarray    # (B,) int  finite samples seen
    last: jnp.ndarray     # (B,)      latest level
    err: jnp.ndarray      # (B, E)    RLS residual ring
    err_n: jnp.ndarray    # (B,) int  residuals pushed


class _ArimaParams(NamedTuple):
    p: jnp.ndarray        # (B,) int  AR order
    d: jnp.ndarray        # (B,) int  differencing order
    lam: jnp.ndarray      # (B,)      forgetting factor
    ridge: jnp.ndarray    # (B,)      initial covariance scale


def _ring_push(ring: jnp.ndarray, n: jnp.ndarray, value: jnp.ndarray,
               do: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter ``value`` into each row's next ring slot where ``do``."""
    width = ring.shape[1]
    oh = (jax.nn.one_hot(n % width, width, dtype=ring.dtype)
          * do[:, None].astype(ring.dtype))
    return ring * (1.0 - oh) + oh * value[:, None], n + do.astype(n.dtype)


def _arima_phi(lags: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """Masked regression vector [active lags, bias] — padded dims read 0."""
    B, p_max = lags.shape
    dims = jnp.arange(p_max)[None, :] < p[:, None]
    return jnp.concatenate([jnp.where(dims, lags, 0.0),
                            jnp.ones((B, 1), lags.dtype)], axis=1)


def _arima_step_core(core, params: _ArimaParams,
                     values: jnp.ndarray, staged: jnp.ndarray,
                     use_pallas: bool = False):
    """One masked online step for every stream (mirror of
    :meth:`repro.core.forecast.OnlineARIMA.update`), minus the residual
    ring — callers push ``(resid, do_rls)`` themselves (the chunked path
    batches all of a chunk's pushes into one scatter)."""
    w, P, lags, tails, count, last = core
    p, d, lam, ridge = params
    B, k = w.shape
    p_max, d_max = k - 1, tails.shape[1]
    finite = jnp.isfinite(values)
    valid = staged & finite
    v = jnp.where(finite, values, 0.0)

    # Incremental differencing cascade: diffs[j] = the new sample's
    # j-times-differenced value, from the per-order tails.
    diffs = [v]
    for j in range(d_max):
        diffs.append(diffs[j] - tails[:, j])
    target = jnp.take_along_axis(jnp.stack(diffs, axis=1),
                                 d[:, None], axis=1)[:, 0]

    phi = _arima_phi(lags, p)
    if use_pallas:
        from ..kernels import ops
        gain, P_new = ops.rls_rank1_update(P, phi, lam)
    else:
        from ..kernels.ref import rls_rank1_update_ref
        gain, P_new = rls_rank1_update_ref(P, phi, lam)
    resid = target - jnp.einsum("bi,bi->b", w, phi)
    w_new = w + gain * resid[:, None]
    # Re-symmetrize (the rank-1 downdate is symmetric in exact arithmetic;
    # roundoff would otherwise accumulate into an indefinite P), then apply
    # the anti-windup trace clamp over the active dims (see
    # :data:`repro.core.forecast.P_TRACE_CAP`).
    P_new = 0.5 * (P_new + jnp.swapaxes(P_new, 1, 2))
    dims = jnp.arange(p_max)[None, :] < p[:, None]
    adim = jnp.concatenate([dims, jnp.ones((B, 1), bool)], axis=1)
    diag = jnp.diagonal(P_new, axis1=1, axis2=2)
    tr = jnp.sum(jnp.where(adim, diag, 0.0), axis=1)
    cap = ridge * (p + 1).astype(P.dtype) * P_TRACE_CAP
    P_new = P_new * jnp.where(tr > cap, cap / tr, 1.0)[:, None, None]
    # Padded dims stay pinned at their ridge * I initialization (the /λ in
    # the covariance update would otherwise inflate them without bound).
    P_pin = ridge[:, None, None] * jnp.eye(k, dtype=P.dtype)
    P_new = jnp.where(adim[:, :, None] & adim[:, None, :], P_new, P_pin)
    # Safety net, mirroring the scalar oracle: a diverged stream restarts
    # its tracker from the prior instead of poisoning later updates.
    ok = (jnp.all(jnp.isfinite(w_new), axis=1)
          & jnp.all(jnp.isfinite(P_new), axis=(1, 2)))
    w_new = jnp.where(ok[:, None], w_new, 0.0)
    P_new = jnp.where(ok[:, None, None], P_new, P_pin)

    # RLS fires once p + d + 1 samples exist (count is pre-increment).
    do_rls = valid & (count >= p + d)
    w = jnp.where(do_rls[:, None], w_new, w)
    P = jnp.where(do_rls[:, None, None], P_new, P)

    # The differenced series gains a value once count >= d.
    defined = valid & (count >= d)
    shifted = jnp.concatenate([target[:, None], lags[:, :-1]], axis=1)
    lags = jnp.where(defined[:, None], shifted, lags)
    for j in range(d_max):
        upd = valid & (count >= j) & (j < d)
        tails = tails.at[:, j].set(jnp.where(upd, diffs[j], tails[:, j]))
    last = jnp.where(valid, v, last)
    count = count + valid.astype(count.dtype)
    return (w, P, lags, tails, count, last), resid, do_rls


def _arima_step(state: _ArimaState, params: _ArimaParams,
                values: jnp.ndarray, staged: jnp.ndarray,
                use_pallas: bool = False) -> _ArimaState:
    """One masked online step for every stream, ring push included."""
    core = (state.w, state.P, state.lags, state.tails, state.count,
            state.last)
    core, resid, do = _arima_step_core(core, params, values, staged,
                                       use_pallas)
    err, err_n = _ring_push(state.err, state.err_n, resid, do)
    return _ArimaState(*core, err=err, err_n=err_n)


def _arima_roll(state: _ArimaState, params: _ArimaParams,
                steps: int) -> jnp.ndarray:
    """Iterated multistep rollout for every stream as a ``lax.scan``."""
    w, _P, lags0, tails0, count, last, _err, _err_n = state
    p, d, _lam, _ridge = params
    B, p_max = lags0.shape
    d_max = tails0.shape[1]
    # Stability guard, mirroring the scalar oracle (ROLLOUT_DIFF_CAP).
    dims = jnp.arange(p_max)[None, :] < p[:, None]
    lim = ROLLOUT_DIFF_CAP * jnp.maximum(
        1.0, jnp.max(jnp.where(dims, jnp.abs(lags0), 0.0), axis=1))

    def step(carry, _):
        lags, tails = carry
        dnext = jnp.clip(jnp.einsum("bi,bi->b", w, _arima_phi(lags, p)),
                         -lim, lim)
        # Invert the d-th difference by cascading through every order.
        vacc, vals = dnext, {}
        for j in range(d_max - 1, -1, -1):
            vacc = jnp.where(j < d, vacc + tails[:, j], vacc)
            vals[j] = vacc
        tails = jnp.stack([jnp.where(j < d, vals[j], tails[:, j])
                           for j in range(d_max)], axis=1)
        lags = jnp.concatenate([dnext[:, None], lags[:, :-1]], axis=1)
        return (lags, tails), vacc

    _, levels = jax.lax.scan(step, (lags0, tails0), None, length=steps)
    out = levels.T
    has_model = count >= p + d + 1
    flat = jnp.where(count > 0, last, 0.0)
    return jnp.where(has_model[:, None], out, flat[:, None])


def _arima_chunk(state: _ArimaState, params: _ArimaParams,
                 vals: jnp.ndarray, use_pallas: bool = False) -> _ArimaState:
    """Apply a (T, B) chunk of queued ticks as one ``lax.scan`` dispatch.

    NaN is the not-staged sentinel: a NaN sample is skipped by the update
    anyway, so no separate mask needs to cross the host boundary. The
    residual-ring writes are hoisted out of the scan: slot order within a
    chunk is deterministic, so all pushes land in one batched scatter
    (T <= queue cap < ring width, hence no intra-chunk slot collisions)."""
    core0 = (state.w, state.P, state.lags, state.tails, state.count,
             state.last)

    def body(c, v):
        c2, resid, do = _arima_step_core(c, params, v, jnp.isfinite(v),
                                         use_pallas)
        return c2, (resid, do)

    core, (resids, dos) = jax.lax.scan(body, core0, vals)
    E = state.err.shape[1]
    ranks = jnp.cumsum(dos.astype(state.err_n.dtype), axis=0) - 1   # (T, B)
    slots = jnp.where(dos, (state.err_n[None, :] + ranks) % E, E)   # E=drop
    rows = jnp.broadcast_to(jnp.arange(dos.shape[1])[None, :], dos.shape)
    err = state.err.at[rows.ravel(), slots.ravel()].set(resids.ravel(),
                                                        mode="drop")
    err_n = state.err_n + jnp.sum(dos, axis=0).astype(state.err_n.dtype)
    return _ArimaState(*core, err=err, err_n=err_n)


def _arima_chunk_roll(state: _ArimaState, params: _ArimaParams,
                      vals: jnp.ndarray, steps: int,
                      use_pallas: bool = False):
    """Fused chunk replay + rollout: one dispatch per read epoch."""
    state = _arima_chunk(state, params, vals, use_pallas)
    return state, _arima_roll(state, params, steps)


# Chunk dispatches rebind ``self.state`` to their output, so the old state
# pytree is donated: every flush updates the bank's buffers in place instead
# of allocating a second copy of the (B, k, k) covariances per tick (pinned
# by the FORECAST_BACKENDS "bank" compilation contract, donation=True).
# ``_*_roll_jit`` reads state without rebinding — donating there would
# invalidate the live buffers.
_arima_chunk_jit = partial(jax.jit, static_argnames=("use_pallas",),
                           donate_argnums=(0,))(_arima_chunk)
_arima_roll_jit = partial(jax.jit, static_argnames=("steps",))(_arima_roll)
_arima_chunk_roll_jit = partial(
    jax.jit, static_argnames=("steps", "use_pallas"),
    donate_argnums=(0,))(_arima_chunk_roll)


# ---------------------------------------------------------------------------
# Holt(-Winters) family: additive level + trend (+ seasonal ring)
# ---------------------------------------------------------------------------

class _HoltState(NamedTuple):
    level: jnp.ndarray    # (B,)
    trend: jnp.ndarray    # (B,)
    seas: jnp.ndarray     # (B, m_max) additive seasonal ring
    count: jnp.ndarray    # (B,) int
    last: jnp.ndarray     # (B,)
    err: jnp.ndarray      # (B, E)
    err_n: jnp.ndarray    # (B,) int


class _HoltParams(NamedTuple):
    alpha: jnp.ndarray
    beta: jnp.ndarray
    gamma: jnp.ndarray
    season: jnp.ndarray   # (B,) int, 0 = no seasonality


def _holt_step(state: _HoltState, params: _HoltParams,
               values: jnp.ndarray, staged: jnp.ndarray) -> _HoltState:
    level, trend, seas, count, last, err, err_n = state
    alpha, beta, gamma, season = params
    m_max = seas.shape[1]
    finite = jnp.isfinite(values)
    valid = staged & finite
    v = jnp.where(finite, values, 0.0)

    has = season > 0
    idx = count % jnp.maximum(season, 1)
    s_old = jnp.take_along_axis(seas, idx[:, None], axis=1)[:, 0] \
        * has.astype(seas.dtype)
    err, err_n = _ring_push(err, err_n, v - (level + trend + s_old),
                            valid & (count > 0))

    prev = level + trend
    lvl_new = alpha * (v - s_old) + (1.0 - alpha) * prev
    tr_new = beta * (lvl_new - level) + (1.0 - beta) * trend
    lvl_new = jnp.where(count == 0, v, lvl_new)
    tr_new = jnp.where(count == 0, 0.0, tr_new)
    s_val = gamma * (v - lvl_new) + (1.0 - gamma) * s_old
    wr = valid & has & (count > 0)
    ohm = (jax.nn.one_hot(idx, m_max, dtype=seas.dtype)
           * wr[:, None].astype(seas.dtype))
    seas = seas * (1.0 - ohm) + ohm * s_val[:, None]

    level = jnp.where(valid, lvl_new, level)
    trend = jnp.where(valid, tr_new, trend)
    last = jnp.where(valid, v, last)
    count = count + valid.astype(count.dtype)
    return _HoltState(level, trend, seas, count, last, err, err_n)


def _holt_roll(state: _HoltState, params: _HoltParams,
               steps: int) -> jnp.ndarray:
    level, trend, seas, count, last, _err, _err_n = state
    _alpha, _beta, _gamma, season = params
    ks = jnp.arange(1, steps + 1, dtype=level.dtype)
    out = level[:, None] + ks[None, :] * trend[:, None]
    idx = (count[:, None] + jnp.arange(steps)[None, :]) \
        % jnp.maximum(season, 1)[:, None]
    out = out + jnp.take_along_axis(seas, idx, axis=1) \
        * (season > 0)[:, None].astype(seas.dtype)
    return jnp.where(count[:, None] > 0, out, 0.0)


def _holt_chunk(state: _HoltState, params: _HoltParams,
                vals: jnp.ndarray) -> _HoltState:
    def body(st, v):
        return _holt_step(st, params, v, jnp.isfinite(v)), None
    return jax.lax.scan(body, state, vals)[0]


def _holt_chunk_roll(state: _HoltState, params: _HoltParams,
                     vals: jnp.ndarray, steps: int):
    state = _holt_chunk(state, params, vals)
    return state, _holt_roll(state, params, steps)


_holt_chunk_jit = jax.jit(_holt_chunk, donate_argnums=(0,))
_holt_roll_jit = partial(jax.jit, static_argnames=("steps",))(_holt_roll)
_holt_chunk_roll_jit = partial(jax.jit, static_argnames=("steps",),
                               donate_argnums=(0,))(_holt_chunk_roll)


# ---------------------------------------------------------------------------
# Seasonal-naive family: replay the last season
# ---------------------------------------------------------------------------

class _SNaiveState(NamedTuple):
    ring: jnp.ndarray     # (B, m_max) circular: slot j holds time ≡ j (mod m)
    count: jnp.ndarray    # (B,) int
    last: jnp.ndarray     # (B,)
    err: jnp.ndarray      # (B, E)
    err_n: jnp.ndarray    # (B,) int


class _SNaiveParams(NamedTuple):
    season: jnp.ndarray   # (B,) int >= 1


def _snaive_step(state: _SNaiveState, params: _SNaiveParams,
                 values: jnp.ndarray, staged: jnp.ndarray) -> _SNaiveState:
    ring, count, last, err, err_n = state
    season = params.season
    m_max = ring.shape[1]
    finite = jnp.isfinite(values)
    valid = staged & finite
    v = jnp.where(finite, values, 0.0)

    idx = count % season
    one_ago = jnp.take_along_axis(ring, idx[:, None], axis=1)[:, 0]
    pred = jnp.where(count >= season, one_ago, last)
    err, err_n = _ring_push(err, err_n, v - pred, valid & (count > 0))
    ohm = (jax.nn.one_hot(idx, m_max, dtype=ring.dtype)
           * valid[:, None].astype(ring.dtype))
    ring = ring * (1.0 - ohm) + ohm * v[:, None]
    last = jnp.where(valid, v, last)
    count = count + valid.astype(count.dtype)
    return _SNaiveState(ring, count, last, err, err_n)


def _snaive_roll(state: _SNaiveState, params: _SNaiveParams,
                 steps: int) -> jnp.ndarray:
    ring, count, last, _err, _err_n = state
    season = params.season
    idx = (count[:, None] + jnp.arange(steps)[None, :]) % season[:, None]
    out = jnp.take_along_axis(ring, idx, axis=1)
    out = jnp.where(count[:, None] >= season[:, None], out, last[:, None])
    return jnp.where(count[:, None] > 0, out, 0.0)


def _snaive_chunk(state: _SNaiveState, params: _SNaiveParams,
                  vals: jnp.ndarray) -> _SNaiveState:
    def body(st, v):
        return _snaive_step(st, params, v, jnp.isfinite(v)), None
    return jax.lax.scan(body, state, vals)[0]


def _snaive_chunk_roll(state: _SNaiveState, params: _SNaiveParams,
                       vals: jnp.ndarray, steps: int):
    state = _snaive_chunk(state, params, vals)
    return state, _snaive_roll(state, params, steps)


_snaive_chunk_jit = jax.jit(_snaive_chunk, donate_argnums=(0,))
_snaive_roll_jit = partial(jax.jit, static_argnames=("steps",))(_snaive_roll)
_snaive_chunk_roll_jit = partial(
    jax.jit, static_argnames=("steps",), donate_argnums=(0,))(_snaive_chunk_roll)


def jit_cache_size() -> int:
    """Combined dispatch-cache size of every family's jitted entry point.

    Growth between two samples means a flush/rollout dispatch paid a fresh
    trace+compile — :class:`ForecastBank` uses it to book that wall into
    ``compile_wall_s`` instead of the steady-state counters (same
    ``_cache_size()`` signal as ``analysis.contracts.count_traces``).
    """
    return sum(int(f._cache_size()) for f in (
        _arima_chunk_jit, _arima_roll_jit, _arima_chunk_roll_jit,
        _holt_chunk_jit, _holt_roll_jit, _holt_chunk_roll_jit,
        _snaive_chunk_jit, _snaive_roll_jit, _snaive_chunk_roll_jit))


# ---------------------------------------------------------------------------
# family banks: padded state + staging + one masked dispatch per flush
# ---------------------------------------------------------------------------

#: Per-stream staging queue depth; a full queue forces an early flush.
_QUEUE_CAP = 128


class _FamilyBank:
    """Shared staging / flush / read plumbing for one forecaster family.

    Updates are write-behind batched in *time* as well as across streams:
    ``stage`` appends to a per-stream queue and ``flush`` replays the whole
    queued chunk through one jitted ``lax.scan`` dispatch. Under the sweep
    cadences (ingest every metric interval, forecasts read every
    optimization/profiling interval) that amortizes the XLA dispatch over
    ~10 ticks on top of the cross-stream batching.
    """

    def __init__(self, rows: Sequence[dict], use_pallas: bool = False,
                 devices: Optional[int] = None):
        self.n = len(rows)
        self.b = bucket_pow2(self.n, minimum=1)
        self.use_pallas = use_pallas
        # Optional scenario-mesh layout: the stream axis is padded to the
        # mesh size and every state/param array is laid out with
        # NamedSharding(mesh, P("scenario", ...)), so the chunked lax.scan
        # dispatches partition across devices (streams are independent —
        # no collectives). None = single-device (the default placement).
        self._mesh = None
        if devices is not None and devices > 1:
            from ..distributed.mesh import pad_to_multiple, scenario_mesh
            self._mesh = scenario_mesh(devices)
            self.b = pad_to_multiple(self.b, int(self._mesh.devices.size))
        # Per-stream staging queues (plain lists: appends are the per-tick
        # hot path; the padded array is only built per flush).
        self._q: List[List[float]] = [[] for _ in range(self.b)]
        with jax.enable_x64():
            self.state, self.params = self._build(list(rows))
            # Host-side snapshot of the initial state for partial resets
            # (reset_rows): self.state's device buffers are donated on every
            # flush, so a bare reference would be invalidated — copy out.
            self._state0 = jax.tree.map(
                lambda a: np.array(a), self.state)
            if self._mesh is not None:
                self.state = self._shard_streams(self.state)
                self.params = self._shard_streams(self.params)

    def _shard_streams(self, tree):
        """Lay a NamedTuple of ``[B, ...]`` arrays out over the mesh."""
        from ..distributed.mesh import scenario_sharding
        return jax.tree.map(
            lambda a: jax.device_put(
                a, scenario_sharding(self._mesh, np.ndim(a))), tree)

    def _chunk_to_device(self, vals: np.ndarray) -> jnp.ndarray:
        """Stage a (T, B) chunk; stream axis sharded to match the state."""
        if self._mesh is None:
            return jnp.asarray(vals)
        from jax.sharding import NamedSharding, PartitionSpec
        from ..distributed.mesh import SCENARIO
        return jax.device_put(
            vals, NamedSharding(self._mesh, PartitionSpec(None, SCENARIO)))

    # family-specific
    def _build(self, rows: List[dict]):
        raise NotImplementedError

    def _chunk(self, vals):
        """Apply a (T, B) chunk of queued values (NaN = not staged)."""
        raise NotImplementedError

    def _chunk_roll(self, vals, steps: int):
        """Fused: apply a (T, B) chunk, then roll out ``steps`` ahead."""
        raise NotImplementedError

    def _roll(self, steps: int):
        raise NotImplementedError

    # shared
    def stage(self, i: int, value: float) -> None:
        self._q[i].append(value)

    def queue_full(self, i: int) -> bool:
        return len(self._q[i]) >= _QUEUE_CAP

    @property
    def has_staged(self) -> bool:
        return any(self._q)

    def _take_chunk(self) -> Tuple[int, np.ndarray]:
        """Drain the queues into a (T, B) chunk array.

        The chunk length is bucketed for jit-cache stability (exact below
        4, multiples of 4 beyond — pow2 buckets waste up to half the scan
        on padding at the sweep's ~10-tick read cadence). NaN marks
        not-staged slots (a NaN observation is a no-op for every family,
        so staged == isfinite); the buffer is freshly allocated, so the
        (possibly zero-copy) device transfer never races a mutation."""
        qs = self._q
        n = sum(len(q) for q in qs)
        t_max = max(len(q) for q in qs)
        tb = t_max if t_max <= 4 else -(-t_max // 4) * 4
        vals = np.full((tb, self.b), np.nan)
        for i, q in enumerate(qs):
            if q:
                vals[:len(q), i] = q
        self._q = [[] for _ in range(self.b)]
        return n, vals

    def flush(self) -> int:
        if not any(self._q):
            return 0
        n, vals = self._take_chunk()
        with jax.enable_x64():
            self.state = self._chunk(self._chunk_to_device(vals))
        return n

    def flush_and_roll(self, steps: int) -> Tuple[int, np.ndarray]:
        """Apply the queued chunk and roll out, fused into one dispatch."""
        if not any(self._q):
            return 0, self.rollout(steps)
        n, vals = self._take_chunk()
        with jax.enable_x64():
            self.state, out = self._chunk_roll(self._chunk_to_device(vals),
                                               steps)
        return n, np.asarray(out)

    def rollout(self, steps: int) -> np.ndarray:
        with jax.enable_x64():
            out = self._roll(steps)
        return np.asarray(out)

    def reset_rows(self, idx: Sequence[int]) -> None:
        """Return streams ``idx`` to their just-constructed state.

        The incremental entry point a long-running service needs: a fleet
        slot freed by one job and reused by another must not leak the old
        job's forecaster state. One tree-scatter over the stacked state
        arrays (parameters are untouched — the row keeps its configured
        family/order), and the rows' staging queues are dropped.
        """
        if len(idx) == 0:
            return
        rows = np.asarray(sorted(idx), dtype=np.int64)
        with jax.enable_x64():
            take = jnp.asarray(rows)
            self.state = type(self.state)(*(
                cur.at[take].set(jnp.asarray(init[rows]))
                for cur, init in zip(self.state, self._state0)))
        for i in rows:
            self._q[int(i)] = []

    def n_observed(self, i: int) -> int:
        return int(self.state.count[i])

    def last(self, i: int) -> float:
        return float(self.state.last[i])

    def residual_std(self, i: int) -> float:
        c = min(int(self.state.err_n[i]), self.state.err.shape[1])
        if c < 4:
            return float("inf")
        return float(np.std(np.asarray(self.state.err[i])[:c]))


class _ArimaBank(_FamilyBank):
    kind = "arima"

    def _build(self, rows: List[dict]):
        rows = rows + [dict(p=1, d=0)] * (self.b - self.n)
        p = np.array([r.get("p", 8) for r in rows], np.int64)
        d = np.array([r.get("d", 1) for r in rows], np.int64)
        lam = np.array([r.get("forgetting", 0.995) for r in rows])
        ridge = np.array([r.get("ridge", 10.0) for r in rows])
        p_max = bucket_pow2(int(p.max()), minimum=4)
        d_max = max(int(d.max()), 1)
        k = p_max + 1
        state = _ArimaState(
            w=jnp.zeros((self.b, k)),
            P=jnp.asarray(ridge[:, None, None] * np.eye(k)[None]),
            lags=jnp.zeros((self.b, p_max)),
            tails=jnp.zeros((self.b, d_max)),
            count=jnp.zeros(self.b, jnp.int64),
            last=jnp.zeros(self.b),
            err=jnp.zeros((self.b, ERR_WINDOW)),
            err_n=jnp.zeros(self.b, jnp.int64))
        params = _ArimaParams(jnp.asarray(p), jnp.asarray(d),
                              jnp.asarray(lam), jnp.asarray(ridge))
        return state, params

    def _chunk(self, vals):
        return _arima_chunk_jit(self.state, self.params, vals,
                                use_pallas=self.use_pallas)

    def _chunk_roll(self, vals, steps):
        return _arima_chunk_roll_jit(self.state, self.params, vals,
                                     steps=steps,
                                     use_pallas=self.use_pallas)

    def _roll(self, steps):
        return _arima_roll_jit(self.state, self.params, steps=steps)


class _HoltBank(_FamilyBank):
    kind = "holt"

    def _build(self, rows: List[dict]):
        rows = rows + [dict()] * (self.b - self.n)
        alpha = np.array([r.get("alpha", 0.5) for r in rows])
        beta = np.array([r.get("beta", 0.1) for r in rows])
        gamma = np.array([r.get("gamma", 0.1) for r in rows])
        season = np.array([r.get("season", 0) for r in rows], np.int64)
        m_max = bucket_pow2(max(int(season.max()), 1), minimum=1)
        state = _HoltState(
            level=jnp.zeros(self.b), trend=jnp.zeros(self.b),
            seas=jnp.zeros((self.b, m_max)),
            count=jnp.zeros(self.b, jnp.int64), last=jnp.zeros(self.b),
            err=jnp.zeros((self.b, ERR_WINDOW)),
            err_n=jnp.zeros(self.b, jnp.int64))
        params = _HoltParams(jnp.asarray(alpha), jnp.asarray(beta),
                             jnp.asarray(gamma), jnp.asarray(season))
        return state, params

    def _chunk(self, vals):
        return _holt_chunk_jit(self.state, self.params, vals)

    def _chunk_roll(self, vals, steps):
        return _holt_chunk_roll_jit(self.state, self.params, vals,
                                    steps=steps)

    def _roll(self, steps):
        return _holt_roll_jit(self.state, self.params, steps=steps)


class _SNaiveBank(_FamilyBank):
    kind = "seasonal"

    def _build(self, rows: List[dict]):
        rows = rows + [dict(season=1)] * (self.b - self.n)
        season = np.array([r.get("season", 12) for r in rows], np.int64)
        if (season < 1).any():
            raise ValueError("SeasonalNaive needs season >= 1")
        m_max = bucket_pow2(int(season.max()), minimum=1)
        state = _SNaiveState(
            ring=jnp.zeros((self.b, m_max)),
            count=jnp.zeros(self.b, jnp.int64), last=jnp.zeros(self.b),
            err=jnp.zeros((self.b, ERR_WINDOW)),
            err_n=jnp.zeros(self.b, jnp.int64))
        return state, _SNaiveParams(jnp.asarray(season))

    def _chunk(self, vals):
        return _snaive_chunk_jit(self.state, self.params, vals)

    def _chunk_roll(self, vals, steps):
        return _snaive_chunk_roll_jit(self.state, self.params, vals,
                                      steps=steps)

    def _roll(self, steps):
        return _snaive_roll_jit(self.state, self.params, steps=steps)


_FAMILY_BANKS = {"arima": _ArimaBank, "holt": _HoltBank,
                 "seasonal": _SNaiveBank}


# ---------------------------------------------------------------------------
# the public bank
# ---------------------------------------------------------------------------

class BankedForecaster:
    """One stream's view into a :class:`ForecastBank`.

    Implements the scalar zoo protocol (``update`` / ``forecast`` /
    ``residual_std`` / ``last`` / ``n_observed``), so a
    :class:`~repro.core.demeter.DemeterController` can hold one as its TSF
    transparently. ``update`` *stages* the observation; the bank applies all
    staged streams in one dispatch on :meth:`ForecastBank.flush` (or lazily
    on the first read).
    """

    def __init__(self, bank: "ForecastBank", row: int):
        self.bank = bank
        self.row = row
        kind, self._i = bank._rows[row]
        self._fam = bank._fams[kind]

    def update(self, value: float) -> None:
        # Inlined ForecastBank.stage — this is the per-tick hot path.
        q = self._fam._q[self._i]
        if len(q) >= _QUEUE_CAP:
            self.bank.flush()
            q = self._fam._q[self._i]
        q.append(value)

    def forecast(self, steps: int) -> np.ndarray:
        return self.bank.forecast_row(self.row, steps)

    def binned(self, horizon: int, bins: int) -> float:
        """Max-bin forecast average (paper §2.2), served from the bank's
        shared batched computation (see :meth:`ForecastBank.binned_row`)."""
        return self.bank.binned_row(self.row, horizon, bins)

    def residual_std(self) -> float:
        self.bank.flush()
        fam, i = self.bank._rows[self.row]
        return self.bank._fams[fam].residual_std(i)

    @property
    def n_observed(self) -> int:
        self.bank.flush()
        fam, i = self.bank._rows[self.row]
        return self.bank._fams[fam].n_observed(i)

    def last(self) -> float:
        self.bank.flush()
        fam, i = self.bank._rows[self.row]
        return self.bank._fams[fam].last(i)


class ForecastBank:
    """All scenarios' online forecasters behind one batched update.

    Build with :meth:`from_kinds`; hand each scenario its
    :class:`BankedForecaster` view. Staged updates are applied per family in
    a single masked jitted dispatch; rollouts for the shared ``horizon`` are
    computed for the whole bank at once and served from cache until the next
    update, so N scenarios reading forecasts in one tick cost one dispatch,
    not N.
    """

    def __init__(self, kinds: Sequence[str],
                 params: Optional[Sequence[dict]] = None,
                 horizon: int = 10, use_pallas: bool = False,
                 devices: Optional[int] = None):
        if not kinds:
            raise ValueError("ForecastBank needs at least one stream")
        params = list(params) if params is not None else [{}] * len(kinds)
        if len(params) != len(kinds):
            raise ValueError("params must align with kinds")
        for k in kinds:
            if k not in FORECASTER_KINDS:
                raise ValueError(f"unknown forecaster kind {k!r}; "
                                 f"available: {FORECASTER_KINDS}")
        self.horizon = int(horizon)
        grouped: Dict[str, List[Tuple[int, dict]]] = {}
        for row, (kind, kw) in enumerate(zip(kinds, params)):
            grouped.setdefault(kind, []).append(
                (row, {**FORECASTER_DEFAULTS[kind], **kw}))
        self._rows: List[Tuple[str, int]] = [("", 0)] * len(kinds)
        self._fams: Dict[str, _FamilyBank] = {}
        for kind, members in grouped.items():
            for i, (row, _) in enumerate(members):
                self._rows[row] = (kind, i)
            self._fams[kind] = _FAMILY_BANKS[kind](
                [kw for _, kw in members], use_pallas=use_pallas,
                devices=devices)
        self._cache: Dict[str, np.ndarray] = {}
        #: wall-clock spent in batched update / rollout dispatches; walls
        #: of dispatches that paid a fresh trace+compile land in
        #: ``compile_wall_s`` instead (first-dispatch split)
        self.update_wall_s = 0.0
        self.rollout_wall_s = 0.0
        self.compile_wall_s = 0.0
        self.n_updates = 0

    def _book_wall(self, attr: str, t0: float, cache0: int) -> None:
        """Accumulate a dispatch wall into ``attr``, or into
        ``compile_wall_s`` when the dispatch grew the jit cache."""
        wall = time.perf_counter() - t0
        if jit_cache_size() > cache0:
            self.compile_wall_s += wall
        else:
            setattr(self, attr, getattr(self, attr) + wall)

    @classmethod
    def from_kinds(cls, kinds: Sequence[str], *,
                   params: Optional[Sequence[dict]] = None,
                   horizon: int = 10, use_pallas: bool = False,
                   devices: Optional[int] = None) -> "ForecastBank":
        return cls(kinds, params=params, horizon=horizon,
                   use_pallas=use_pallas, devices=devices)

    @property
    def n_streams(self) -> int:
        return len(self._rows)

    def view(self, row: int) -> BankedForecaster:
        return BankedForecaster(self, row)

    def views(self) -> List[BankedForecaster]:
        return [self.view(r) for r in range(self.n_streams)]

    def device_buffers(self) -> Dict[str, object]:
        """Every family's state arrays, by ``"<kind>.<field>"``."""
        return {f"{kind}.{field}": arr for kind, fam in self._fams.items()
                for field, arr in fam.state._asdict().items()}

    # -- updates -------------------------------------------------------------
    def stage(self, row: int, value: float) -> None:
        fam, i = self._rows[row]
        if self._fams[fam].queue_full(i):
            self.flush()
        self._fams[fam].stage(i, value)

    def flush(self) -> int:
        """Apply every staged stream: one masked dispatch per family."""
        if not any(f.has_staged for f in self._fams.values()):
            return 0
        t0 = time.perf_counter()
        cache0 = jit_cache_size()
        n = 0
        with obs.timed_phase("forecast", "forecast.flush",
                             streams=self.n_streams):
            for kind, fam in self._fams.items():
                if fam.has_staged:
                    n += fam.flush()
                    self._drop_family_cache(kind)
        self._book_wall("update_wall_s", t0, cache0)
        if obs.enabled():
            obs.inc("sweep.forecast_flushes")
            obs.inc("sweep.forecast_updates", n)
            obs.track_jit_cache("forecast_bank", jit_cache_size())
        self.n_updates += n
        return n

    def reset_rows(self, rows: Sequence[int]) -> int:
        """Reset streams ``rows`` to their just-constructed state (see
        :meth:`_FamilyBank.reset_rows`) — one scatter per touched family.

        Returns the number of streams reset. A fleet service calls this in
        one batch per epoch for every slot freed-and-reused since the last
        epoch, so slot churn costs O(families) dispatches, not O(jobs).
        """
        by_fam: Dict[str, List[int]] = {}
        for row in rows:
            fam, i = self._rows[row]
            by_fam.setdefault(fam, []).append(i)
        n = 0
        with obs.timed_phase("forecast", "forecast.reset_rows",
                             streams=sum(map(len, by_fam.values()))):
            for fam, members in by_fam.items():
                self._fams[fam].reset_rows(members)
                self._drop_family_cache(fam)
                n += len(members)
        return n

    # -- reads ---------------------------------------------------------------
    def _drop_family_cache(self, fam: str) -> None:
        for k in [k for k in self._cache
                  if k == fam or (isinstance(k, tuple) and k[0] == fam)]:
            del self._cache[k]

    def _cached_rollout(self, fam: str) -> np.ndarray:
        """The family's horizon rollout; a dirty queue flushes *and* rolls
        out in one fused dispatch."""
        f = self._fams[fam]
        if f.has_staged:
            t0 = time.perf_counter()
            cache0 = jit_cache_size()
            with obs.timed_phase("forecast", "forecast.flush_and_roll",
                                 family=fam):
                n, out = f.flush_and_roll(self.horizon)
            self._book_wall("update_wall_s", t0, cache0)
            if obs.enabled():
                obs.inc("sweep.forecast_updates", n)
                obs.track_jit_cache("forecast_bank", jit_cache_size())
            self.n_updates += n
            self._drop_family_cache(fam)
            self._cache[fam] = out
            return out
        cached = self._cache.get(fam)
        if cached is None:
            t0 = time.perf_counter()
            cache0 = jit_cache_size()
            with obs.timed_phase("forecast", "forecast.rollout", family=fam):
                cached = f.rollout(self.horizon)
            self._book_wall("rollout_wall_s", t0, cache0)
            self._cache[fam] = cached
        return cached

    def forecast_row(self, row: int, steps: int) -> np.ndarray:
        fam, i = self._rows[row]
        if steps <= self.horizon:
            return self._cached_rollout(fam)[i, :steps].copy()
        self.flush()
        t0 = time.perf_counter()
        cache0 = jit_cache_size()
        with obs.timed_phase("forecast", "forecast.rollout", family=fam,
                             steps=steps):
            out = self._fams[fam].rollout(steps)[i]
        self._book_wall("rollout_wall_s", t0, cache0)
        return out

    def binned_row(self, row: int, horizon: int, bins: int) -> float:
        """Paper §2.2 max-bin average for one stream, computed for the
        whole family at once and cached until the next update."""
        bins = max(bins, 1)
        fam, i = self._rows[row]
        if horizon != self.horizon or horizon % bins != 0 or horizon < 1:
            # Off-cache shape: mirror the scalar binned_forecast inline
            # (calling it would recurse through this fast path).
            fc = np.maximum(self.forecast_row(row, horizon), 0.0)
            splits = np.array_split(fc, bins)
            means = [float(s.mean()) for s in splits if len(s)]
            return max(means) if means else 0.0
        roll = self._cached_rollout(fam)     # drops stale (fam, bins) keys
        key = (fam, bins)
        cached = self._cache.get(key)
        if cached is None:
            pos = np.maximum(roll, 0.0)
            cached = pos.reshape(len(pos), bins, -1).mean(axis=2).max(axis=1)
            self._cache[key] = cached
        return float(cached[i])


@FORECAST_BACKENDS.register("scalar")
def _scalar_forecaster(kind: str, *, horizon: int = 10,
                       use_pallas: bool = False, **kwargs):
    """Float64 NumPy zoo member (the reference oracle)."""
    del horizon, use_pallas              # scalar zoo members roll out lazily
    return make_scalar_forecaster(kind, **kwargs)


@FORECAST_BACKENDS.register("bank")
def _banked_forecaster(kind: str, *, horizon: int = 10,
                       use_pallas: bool = False, **kwargs):
    """Single-stream :class:`BankedForecaster` over its own bank."""
    return ForecastBank([kind], params=[kwargs], horizon=horizon,
                        use_pallas=use_pallas).view(0)


def make_forecaster(kind: str = "arima", *, backend: str = "bank",
                    horizon: int = 10, use_pallas: bool = False, **kwargs):
    """One forecaster of ``kind`` on the registered ``backend``.

    ``backend="scalar"`` returns the float64 NumPy zoo member (the reference
    oracle); ``backend="bank"`` returns a single-stream
    :class:`BankedForecaster` over its own :class:`ForecastBank`. Third-party
    backends registered in :data:`repro.core.registry.FORECAST_BACKENDS`
    resolve the same way.
    """
    factory = FORECAST_BACKENDS.get(backend)
    return factory(kind, horizon=horizon, use_pallas=use_pallas, **kwargs)


def _bank_forecaster_probes():
    """Contracts for the banked forecaster's two hot dispatches:

    * the fused chunk-replay + rollout (``_arima_chunk_roll_jit``) — the
      per-read-epoch dispatch. State donation must survive compilation
      (every flush updates the bank's buffers in place), float64 is the
      *ceiling by design* (the bank mirrors the float64 NumPy zoo
      bit-for-bit), no callback may hide inside the scan body, and the
      chunk-length bucketing must hold the trace count at the bucket
      count, not the call count;
    * the Pallas RLS kernel lowering (``repro.kernels.rls_update``) —
      checked against the contract colocated with the kernel.
    """
    from ..analysis.contracts import (CompilationContract, ContractProbe,
                                      count_traces)
    from ..kernels.rls_update import rls_contract, rls_rank1_update

    with jax.enable_x64():
        fam = _ArimaBank([dict(p=4, d=1)] * 4)
        state, params = fam.state, fam.params
        chunk = jnp.asarray(np.where(np.arange(8)[:, None] < 6,
                                     np.linspace(1.0, 4.0, 32).reshape(8, 4),
                                     np.nan))
        buckets = {t: jnp.asarray(np.full((t, 4), 2.0)) for t in (4, 8, 12)}

    def _bucketed_traces() -> int:
        # The _take_chunk buckets (exact <= 4, multiples of 4 beyond) must
        # hold the jit cache at #buckets even when flush lengths vary.
        workload = [((state, params, buckets[t]),
                     dict(steps=10, use_pallas=False))
                    for t in (4, 4, 8, 8, 12)]
        return count_traces(_arima_chunk_roll, workload, x64=True,
                            static_argnames=("steps", "use_pallas"))

    chunk_contract = CompilationContract(
        name="forecast backend:bank",
        donation=True,                 # state buffers update in place
        dtype_ceiling="float64",       # mirrors the float64 NumPy zoo
        forbid_callbacks=True,
        max_traces=3,                  # one per chunk-length bucket above
        note="fused ARIMA chunk replay + rollout (one dispatch per read "
             "epoch)")
    chunk_probe = ContractProbe(
        contract=chunk_contract, fn=_arima_chunk_roll_jit,
        args=(state, params, chunk), kwargs=dict(steps=10, use_pallas=False),
        x64=True, traces=_bucketed_traces)

    k = int(state.w.shape[1])
    pallas_probe = ContractProbe(
        contract=rls_contract(),
        fn=rls_rank1_update,
        args=(jnp.eye(k)[None].repeat(8, 0).astype(jnp.float32),
              jnp.ones((8, k), jnp.float32),
              jnp.full((8,), 0.995, jnp.float32)),
        kwargs=dict(interpret=True),
        note="interpret-mode lowering (CPU); Mosaic on TPU")
    return [chunk_probe, pallas_probe]


def _scalar_forecaster_probe():
    from ..analysis.contracts import host_probe
    return host_probe("forecast backend:scalar",
                      "float64 NumPy zoo member — the reference oracle, no "
                      "XLA dispatch")


FORECAST_BACKENDS.attach_contract("bank", _bank_forecaster_probes)
FORECAST_BACKENDS.attach_contract("scalar", _scalar_forecaster_probe)


# ---------------------------------------------------------------------------
# DetectorBank: batched §2.3 anomaly detectors
# ---------------------------------------------------------------------------

def _mad_threshold(ring: jnp.ndarray, rn: jnp.ndarray, k_sigma: jnp.ndarray,
                   warm: jnp.ndarray) -> jnp.ndarray:
    """Streaming median + k·MAD threshold over each row's error ring."""
    E = ring.shape[1]
    cnt = jnp.minimum(rn, E)
    validm = jnp.arange(E)[None, :] < cnt[:, None]
    c = jnp.maximum(cnt, 1)

    def masked_median(x):
        s = jnp.sort(jnp.where(validm, x, jnp.inf), axis=1)
        lo = jnp.take_along_axis(s, ((c - 1) // 2)[:, None], axis=1)[:, 0]
        hi = jnp.take_along_axis(s, (c // 2)[:, None], axis=1)[:, 0]
        return 0.5 * (lo + hi)

    med = masked_median(ring)
    mad = masked_median(jnp.abs(ring - med[:, None])) * 1.4826
    thr = med + k_sigma * jnp.maximum(mad, 1e-9)
    return jnp.where(cnt >= warm, thr, jnp.inf)


# state / ring / rn are rebound to the outputs every sample (the per-tick
# hot path), so their old buffers are donated; params are read-only.
@partial(jax.jit, donate_argnums=(0, 2, 3))
def _detector_observe(state: _ArimaState, params: _ArimaParams,
                      ring: jnp.ndarray, rn: jnp.ndarray,
                      values: jnp.ndarray, active: jnp.ndarray,
                      k_sigma: jnp.ndarray, warm: jnp.ndarray):
    """One sample for every stream: predict, threshold, (conditionally) learn."""
    finite = jnp.isfinite(values)
    act = active & finite
    v = jnp.where(finite, values, 0.0)
    pred = _arima_roll(state, params, 1)[:, 0]
    # A non-finite prediction must neither flag nor enter the healthy-error
    # ring (it would disable the MAD threshold forever) — mirror of the
    # scalar detector's sick-model guard.
    can = (state.count >= warm) & jnp.isfinite(pred)
    err_abs = jnp.abs(v - pred)
    thr = _mad_threshold(ring, rn, k_sigma, warm)
    anomalous = act & can & (err_abs > thr)
    ring, rn = _ring_push(ring, rn, err_abs, act & can & ~anomalous)
    # Positive-executions-only training: coast on the prediction during an
    # anomaly so the outage regime never looks 'normal'.
    used = jnp.where(anomalous, pred, v)
    state = _arima_step(state, params, used, act)
    return state, ring, rn, anomalous


class DetectorBank:
    """B one-step-error anomaly detectors advanced by one dispatch per sample.

    Batched mirror of :class:`repro.core.anomaly.MetricDetector`: each
    stream runs an online-ARIMA identity predictor; the absolute one-step
    error is compared against ``median + k·MAD`` of a fixed-size ring of
    past *healthy* errors. Agreement with the scalar detector (flags and
    episodes) is pinned in ``tests/test_forecast_bank.py``.
    """

    def __init__(self, n_streams: int, *, k_sigma: float = 5.0,
                 min_warmup: int = 12, p: int = 4, d: int = 1,
                 err_window: int = DETECTOR_ERR_WINDOW):
        if n_streams < 1:
            raise ValueError("DetectorBank needs at least one stream")
        self.n = n_streams
        self.b = bucket_pow2(n_streams, minimum=1)
        with jax.enable_x64():
            model = _ArimaBank([dict(p=p, d=d)] * self.b)
            self._state, self._params = model.state, model.params
            self._ring = jnp.zeros((self.b, err_window))
            self._rn = jnp.zeros(self.b, jnp.int64)
            self._k_sigma = jnp.full(self.b, float(k_sigma))
            self._warm = jnp.full(self.b, int(min_warmup), jnp.int64)
        self.wall_s = 0.0
        self.n_samples = 0
        # Host snapshots for reset_rows (observe donates the live buffers).
        self._state0 = jax.tree.map(lambda a: np.array(a), self._state)
        self._ring0 = np.array(self._ring)
        self._rn0 = np.array(self._rn)

    def reset_rows(self, rows: Sequence[int]) -> None:
        """Return detectors ``rows`` to their just-constructed state (the
        fleet-slot-reuse mirror of :meth:`ForecastBank.reset_rows`)."""
        if len(rows) == 0:
            return
        take = np.asarray(sorted(rows), dtype=np.int64)
        with jax.enable_x64():
            idx = jnp.asarray(take)
            self._state = type(self._state)(*(
                cur.at[idx].set(jnp.asarray(init[take]))
                for cur, init in zip(self._state, self._state0)))
            self._ring = self._ring.at[idx].set(jnp.asarray(self._ring0[take]))
            self._rn = self._rn.at[idx].set(jnp.asarray(self._rn0[take]))

    def observe(self, values: np.ndarray,
                active: Optional[np.ndarray] = None) -> np.ndarray:
        """Feed one sample per stream; returns the per-stream anomaly flags.

        ``active=False`` (or a non-finite value) skips that stream entirely,
        like not calling the scalar detector."""
        values = np.asarray(values, np.float64)
        if values.shape != (self.n,):
            raise ValueError(f"expected {self.n} values, got {values.shape}")
        act = np.zeros(self.b, bool)
        act[:self.n] = True if active is None else np.asarray(active, bool)
        vals = np.zeros(self.b)
        vals[:self.n] = values
        t0 = time.perf_counter()
        with obs.timed_phase("detect", "detector.observe", streams=self.n), \
                jax.enable_x64():
            self._state, self._ring, self._rn, flags = _detector_observe(
                self._state, self._params, self._ring, self._rn,
                jnp.asarray(vals), jnp.asarray(act),
                self._k_sigma, self._warm)
        out = np.asarray(flags)[:self.n]
        self.wall_s += time.perf_counter() - t0
        self.n_samples += 1
        if obs.enabled():
            obs.inc("sweep.detector_samples")
            obs.track_jit_cache("detector",
                                int(_detector_observe._cache_size()))
        return out
