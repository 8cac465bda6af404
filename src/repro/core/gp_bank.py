"""Batched GP fitting and prediction for the whole modeling stack.

:class:`GPBank` packs many exact GPs — one per (segment, objective) and, in a
sweep, per scenario — into stacked, zero-padded arrays and fits **all** of
their hyper-parameters in a single vmapped, jitted multi-restart L-BFGS run
(:func:`optax.lbfgs`). This removes the per-GP scipy round-trip from the hot
path: where :meth:`repro.core.gp.GP.fit` pays a Python/scipy loop per model,
``GPBank.fit`` pays one XLA dispatch for the full segment x objective x
scenario batch.

The two paths optimize the *same* masked marginal-likelihood objective from
the *same* restart initializations, so a bank member agrees with the scalar
scipy fit within float32 optimizer tolerance — the scalar path stays in
:mod:`repro.core.gp` as a reference oracle and the agreement is pinned by
``tests/test_gp_bank.py``.

Padding layout: every member is padded to a power-of-two training size.
Padded rows carry ``mask == 0``; the kernel matrix is forced block-diagonal
(identity on the padded block), so the Cholesky factor, ``alpha`` and the
marginal likelihood of the real block are untouched by padding and a member
can be sliced back out as a plain :class:`~repro.core.gp.GP`.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import optax.tree_utils as otu

from .. import obs
from .gp import _JITTER, GP, _matern52, _mm, _unpack, restart_inits

#: Default optimizer budget; mirrors ModelBank's scalar-path settings.
DEFAULT_RESTARTS = 2
DEFAULT_MAX_ITER = 60


def bucket_pow2(n: int, minimum: int = 8) -> int:
    """Next power of two >= n (stabilizes jit cache keys across calls).

    Shared by every batched bank (GPs here, forecasters/detectors in
    :mod:`repro.core.forecast_bank`) for padding batch and window sizes."""
    b = minimum
    while b < n:
        b *= 2
    return b


_bucket = bucket_pow2


def _member_layout(b: int, devices: Optional[int]):
    """Resolve the member-axis layout for a packed bank of ``b`` members.

    ``devices=None`` (or 1) keeps the default single-device placement and
    returns ``(b, None)``. Otherwise the member axis is padded to the
    ``scenario`` mesh size and the returned ``put`` callable lays a packed
    ``[B, ...]`` array out with ``NamedSharding(mesh, P("scenario", ...))``
    — members are independent, so the vmapped fit/posterior dispatches
    partition across devices with no collectives.
    """
    if devices is None or devices <= 1:
        return b, None
    from ..distributed.mesh import (pad_to_multiple, scenario_mesh,
                                    scenario_sharding)
    mesh = scenario_mesh(devices)
    b = pad_to_multiple(b, int(mesh.devices.size))

    def put(a: np.ndarray) -> jnp.ndarray:
        return jax.device_put(a, scenario_sharding(mesh, np.ndim(a)))

    return b, put


# --------------------------------------------------------------------------
# masked objective (identical to gp._neg_mll on the real block)
# --------------------------------------------------------------------------
def _masked_kernel(x: jnp.ndarray, mask: jnp.ndarray,
                   theta: jnp.ndarray) -> jnp.ndarray:
    """``K + (noise + jitter) I`` over the ``mask == 1`` rows, identity on
    the padded block: the real block is the unpadded matrix."""
    ls, signal, noise = _unpack(theta, x.shape[-1])
    k = _matern52(x, x, ls, signal) + (noise + _JITTER) * jnp.eye(x.shape[0])
    m2 = mask[:, None] * mask[None, :]
    return jnp.where(m2 > 0, k, 0.0) + jnp.diag(1.0 - mask)


def _masked_neg_mll(theta: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray,
                    mask: jnp.ndarray) -> jnp.ndarray:
    """Negative log marginal likelihood over the ``mask == 1`` rows only.

    Padded rows are decoupled by zeroing their kernel rows/columns and
    pinning their diagonal to 1, which leaves the Cholesky factor of the
    real block bit-identical to the unpadded computation.
    """
    dim = x.shape[-1]
    k = _masked_kernel(x, mask, theta)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    n_real = jnp.sum(mask)
    mll = (-0.5 * _mm(y, alpha)
           - jnp.sum(jnp.log(jnp.diagonal(chol)) * mask)
           - 0.5 * n_real * jnp.log(2.0 * jnp.pi))
    # Same weak log-normal priors as the scalar path (gp._neg_mll).
    prior = (jnp.sum((theta[:dim] - jnp.log(0.5)) ** 2) / 8.0
             + (theta[dim]) ** 2 / 8.0
             + (theta[dim + 1] - jnp.log(1e-2)) ** 2 / 18.0)
    return -(mll - prior)


# --------------------------------------------------------------------------
# jitted multi-restart L-BFGS over the packed batch
# --------------------------------------------------------------------------
def _lbfgs_minimize(fun, t0: jnp.ndarray, max_iter: int,
                    tol: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Minimize ``fun`` from ``t0`` with optax L-BFGS + zoom linesearch."""
    opt = optax.lbfgs()
    value_and_grad = optax.value_and_grad_from_state(fun)

    def cond(carry):
        _, state = carry
        count = otu.tree_get(state, "count")
        grad = otu.tree_get(state, "grad")
        return (count == 0) | ((count < max_iter)
                               & (otu.tree_norm(grad) > tol))

    def body(carry):
        t, state = carry
        value, grad = value_and_grad(t, state=state)
        updates, state = opt.update(grad, state, t, value=value, grad=grad,
                                    value_fn=fun)
        return optax.apply_updates(t, updates), state

    t, _ = jax.lax.while_loop(cond, body, (t0, opt.init(t0)))
    return t, fun(t)


@partial(jax.jit, static_argnames=("max_iter",))
def _fit_packed(x: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray,
                t0s: jnp.ndarray, max_iter: int):
    """Fit B padded GPs, each from R restarts, in one fused dispatch.

    x: (B, n, d), y: (B, n) standardized, mask: (B, n), t0s: (B, R, d+2).
    Returns best theta (B, d+2), its objective value (B,), and the
    Cholesky/alpha pair of the refitted kernel at the optimum.
    """
    def fit_one(xi, yi, mi, t0s_i):
        def from_start(t0):
            t, v = _lbfgs_minimize(
                lambda th: _masked_neg_mll(th, xi, yi, mi), t0,
                max_iter=max_iter, tol=1e-5)
            return t, v

        ts, vs = jax.vmap(from_start)(t0s_i)
        vs = jnp.where(jnp.isfinite(vs), vs, jnp.inf)
        j = jnp.argmin(vs)
        dim = xi.shape[-1]
        fallback = jnp.concatenate([jnp.zeros(dim), jnp.zeros(1),
                                    jnp.full(1, jnp.log(1e-2))])
        theta = jnp.where(jnp.isfinite(vs[j]), ts[j], fallback)

        chol = jnp.linalg.cholesky(_masked_kernel(xi, mi, theta))
        alpha = jax.scipy.linalg.cho_solve((chol, True), yi)
        return theta, vs[j], chol, alpha

    return jax.vmap(fit_one)(x, y, mask, t0s)


@jax.jit
def _posterior_packed(x: jnp.ndarray, mask: jnp.ndarray, theta: jnp.ndarray,
                      chol: jnp.ndarray, alpha: jnp.ndarray,
                      xq: jnp.ndarray):
    """Standardized posterior of B padded GPs at a shared (m, d) query grid."""
    def one(xi, mi, ti, ci, ai):
        dim = xi.shape[-1]
        ls, signal, _ = _unpack(ti, dim)
        ks = _matern52(xq, xi, ls, signal) * mi[None, :]
        mean = _mm(ks, ai)
        v = jax.scipy.linalg.solve_triangular(ci, ks.T, lower=True)
        var = jnp.maximum(signal - jnp.sum(v * v, axis=0), 1e-10)
        return mean, var

    return jax.vmap(one)(x, mask, theta, chol, alpha)


@jax.jit
def _rgpe_reads_packed(x: jnp.ndarray, mask: jnp.ndarray, theta: jnp.ndarray,
                       chol: jnp.ndarray, alpha: jnp.ndarray,
                       tx: jnp.ndarray, tmask: jnp.ndarray,
                       ttheta: jnp.ndarray):
    """Every read of one RGPE build: the standardized posterior of B padded
    base GPs at the target's padded training inputs ``tx`` (n, d), and the
    diagonal of the target's masked ``(K + (noise + jitter) I)^-1`` (n,),
    whose real block is the inverse of the unpadded matrix."""
    mean, var = _posterior_packed(x, mask, theta, chol, alpha, tx)
    kinv = jnp.linalg.inv(_masked_kernel(tx, tmask, ttheta))
    return mean, var, jnp.diagonal(kinv)


def jit_cache_size() -> int:
    """Combined dispatch-cache size of the bank's jitted entry points.

    Growth between two samples means a fresh trace+compile happened in
    between — callers (ModelBank, the sweep engine) use it to split
    compile wall out of steady-state fit wall, the same ``_cache_size()``
    signal ``analysis.contracts.count_traces`` measures.
    """
    return sum(int(f._cache_size()) for f in (
        _fit_packed, _posterior_packed, _rgpe_reads_packed))


@dataclass
class GPBank:
    """A batch of fitted exact GPs sharing one packed representation.

    Construct via :meth:`GPBank.fit`. All members share the input dimension
    ``d``; training-set sizes may differ (padded internally).
    """

    x: np.ndarray        # (B, n_max, d) padded unit-cube inputs
    mask: np.ndarray     # (B, n_max) 1.0 on real rows
    theta: np.ndarray    # (B, d + 2) log hyper-parameters
    chol: np.ndarray     # (B, n_max, n_max) Cholesky of masked K + noise I
    alpha: np.ndarray    # (B, n_max) K^-1 y (standardized)
    y_mean: np.ndarray   # (B,)
    y_std: np.ndarray    # (B,)

    # -- fitting -----------------------------------------------------------
    @staticmethod
    def fit(datasets: Sequence[Tuple[np.ndarray, np.ndarray]], *,
            restarts: int = DEFAULT_RESTARTS,
            seeds: Optional[Sequence[int]] = None,
            max_iter: int = DEFAULT_MAX_ITER,
            devices: Optional[int] = None) -> "GPBank":
        """Fit one GP per ``(x, y)`` dataset in a single jitted batch.

        ``seeds`` controls each member's restart initializations and matches
        :meth:`GP.fit`'s draws, so member ``i`` optimizes from the same
        starting points as ``GP.fit(x_i, y_i, seed=seeds[i])``.

        ``devices`` shards the member axis over a ``scenario`` mesh of that
        many devices (padding the batch to the mesh size), so a sweep's
        shared model-update scales with device count; members fit
        independently, so results do not depend on the layout.
        """
        if not datasets:
            raise ValueError("GPBank.fit needs at least one dataset")
        if seeds is None:
            seeds = [0] * len(datasets)
        if len(seeds) != len(datasets):
            raise ValueError("seeds must align with datasets")

        dims = {np.asarray(x).reshape(len(y), -1).shape[1]
                for x, y in datasets}
        if len(dims) != 1:
            raise ValueError(f"all datasets must share one input dim, "
                             f"got {sorted(dims)}")
        dim = dims.pop()
        # Bucket both batch size and training size to powers of two so the
        # jit cache stays small as banks/segments grow; padded members are
        # dummy single-point datasets sliced off before returning.
        n_real = len(datasets)
        b = _bucket(n_real, minimum=1)
        b, put = _member_layout(b, devices)
        n_max = _bucket(max(len(y) for _, y in datasets))

        xs = np.zeros((b, n_max, dim))
        ys = np.zeros((b, n_max))
        mask = np.zeros((b, n_max))
        y_mean = np.zeros(b)
        y_std = np.ones(b)
        t0s = np.zeros((b, max(restarts, 1), dim + 2))
        mask[:, 0] = 1.0                    # dummy rows: one point at origin
        for i, (x, y) in enumerate(datasets):
            x = np.asarray(x, np.float64).reshape(len(y), -1)
            y = np.asarray(y, np.float64).ravel()
            n = len(y)
            y_mean[i] = y.mean()
            y_std[i] = y.std() or 1.0
            xs[i, :n] = x
            ys[i, :n] = (y - y_mean[i]) / y_std[i]
            mask[i, :n] = 1.0
            t0s[i] = restart_inits(dim, restarts, seeds[i])

        pack = put if put is not None else jnp.asarray
        with obs.timed_phase("fit", "gp_bank.fit",
                             members=n_real, b=b, n_max=n_max):
            theta, _val, chol, alpha = _fit_packed(
                pack(xs), pack(ys), pack(mask), pack(t0s), max_iter=max_iter)
        if obs.enabled():
            obs.inc("sweep.gp_fits", n_real)
            obs.inc("transfer.h2d_bytes",
                    xs.nbytes + ys.nbytes + mask.nbytes + t0s.nbytes)
            obs.track_jit_cache("gp_bank", jit_cache_size())
        keep = slice(0, n_real)
        return GPBank(x=xs[keep], mask=mask[keep],
                      theta=np.asarray(theta)[keep],
                      chol=np.asarray(chol)[keep],
                      alpha=np.asarray(alpha)[keep],
                      y_mean=y_mean[keep], y_std=y_std[keep])

    # -- queries -----------------------------------------------------------
    @property
    def n_members(self) -> int:
        return len(self.theta)

    def counts(self) -> np.ndarray:
        return self.mask.sum(axis=1).astype(int)

    def posterior(self, xq: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """All members' posterior mean/variance (original units) at a shared
        (m, d) query grid. Returns two (B, m) arrays in one jitted call."""
        xq = np.asarray(xq, np.float64).reshape(-1, self.x.shape[-1])
        with obs.span("gp_bank.posterior", members=self.n_members,
                      m=xq.shape[0]):
            mean_s, var_s = _posterior_packed(
                jnp.asarray(self.x), jnp.asarray(self.mask),
                jnp.asarray(self.theta), jnp.asarray(self.chol),
                jnp.asarray(self.alpha), jnp.asarray(xq))
        if obs.enabled():
            obs.track_jit_cache("gp_bank", jit_cache_size())
        mean = np.asarray(mean_s) * self.y_std[:, None] + self.y_mean[:, None]
        var = np.asarray(var_s) * (self.y_std ** 2)[:, None]
        return mean, var

    def member(self, i: int) -> GP:
        """Slice member ``i`` back out as a scalar :class:`GP`.

        Padding keeps the real block of the Cholesky factor exact, so this
        is a cheap view — no refactorization."""
        n = int(self.mask[i].sum())
        return GP(x=self.x[i, :n].copy(),
                  y_mean=float(self.y_mean[i]), y_std=float(self.y_std[i]),
                  theta=self.theta[i].copy(),
                  chol=self.chol[i, :n, :n].copy(),
                  alpha=self.alpha[i, :n].copy())

    def members(self) -> List[GP]:
        return [self.member(i) for i in range(self.n_members)]


def _pack(gps: Sequence[GP], b: int) -> Tuple[np.ndarray, ...]:
    """Fitted GPs as zero-padded ``(x, mask, theta, chol, alpha)`` arrays
    of ``b`` members (``b >= len(gps)``), padded to a power-of-two training
    size with an identity block in each Cholesky factor."""
    dim = gps[0].x.shape[1]
    n_max = _bucket(max(len(g.alpha) for g in gps))
    xs = np.zeros((b, n_max, dim))
    mask = np.zeros((b, n_max))
    theta = np.zeros((b, dim + 2))
    chol = np.tile(np.eye(n_max), (b, 1, 1))
    alpha = np.zeros((b, n_max))
    for i, g in enumerate(gps):
        n = len(g.alpha)
        xs[i, :n] = g.x
        mask[i, :n] = 1.0
        theta[i] = g.theta
        chol[i, :n, :n] = g.chol
        chol[i, n:, :n] = 0.0
        alpha[i, :n] = g.alpha
    return xs, mask, theta, chol, alpha


def batched_posterior(gps: Sequence[GP], xq: np.ndarray,
                      devices: Optional[int] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Posterior mean/variance of arbitrary fitted GPs at a shared grid.

    Packs already-fitted scalar GPs (whatever path produced them) into
    padded arrays and evaluates all posteriors in one jitted call. Returns
    two (len(gps), m) arrays. This is the RGPE/controller fast path: every
    ensemble member is predicted in one dispatch instead of a Python loop.
    ``devices`` shards the member axis over a ``scenario`` mesh (the query
    grid is replicated), like :meth:`GPBank.fit`.
    """
    if not gps:
        raise ValueError("batched_posterior needs at least one GP")
    dim = gps[0].x.shape[1]
    xq = np.asarray(xq, np.float64).reshape(-1, dim)
    b, put = _member_layout(_bucket(len(gps), minimum=1), devices)
    pack = put if put is not None else jnp.asarray
    with obs.span("gp_bank.batched_posterior", members=len(gps),
                  m=xq.shape[0]):
        mean_s, var_s = _posterior_packed(
            *(pack(a) for a in _pack(gps, b)), jnp.asarray(xq))
    if obs.enabled():
        obs.track_jit_cache("gp_bank", jit_cache_size())
    y_std = np.asarray([g.y_std for g in gps])
    y_mean = np.asarray([g.y_mean for g in gps])
    mean = np.asarray(mean_s)[:len(gps)] * y_std[:, None] + y_mean[:, None]
    var = np.asarray(var_s)[:len(gps)] * (y_std ** 2)[:, None]
    return mean, var


def rgpe_reads(bases: Sequence[GP], target: GP,
               devices: Optional[int] = None
               ) -> Tuple[List[Tuple[np.ndarray, np.ndarray]],
                          Tuple[np.ndarray, np.ndarray]]:
    """Every GP read of one RGPE build in one jitted dispatch.

    Returns each base GP's posterior mean and variance at the target's
    training inputs, in original units as :meth:`GP.posterior` gives them,
    and the target's standardized leave-one-out mean and variance as
    :meth:`GP.loo_moments` gives them. The query rows (the target's
    training size) are padded to a power of two, so target sizes share
    programs. ``devices`` shards the base members like
    :func:`batched_posterior`.
    """
    if not bases:
        raise ValueError("rgpe_reads needs at least one base GP")
    n, dim = target.x.shape
    n_pad = _bucket(n)
    tx = np.zeros((n_pad, dim))
    tx[:n] = target.x
    tmask = np.zeros(n_pad)
    tmask[:n] = 1.0
    b, put = _member_layout(_bucket(len(bases), minimum=1), devices)
    pack = put if put is not None else jnp.asarray
    with obs.span("gp_bank.rgpe_reads", members=len(bases), n=n):
        out = _rgpe_reads_packed(
            *(pack(a) for a in _pack(bases, b)), jnp.asarray(tx),
            jnp.asarray(tmask), jnp.asarray(target.theta))
        mean_s, var_s, kinv_diag = jax.device_get(out)
    if obs.enabled():
        obs.track_jit_cache("gp_bank", jit_cache_size())
    reads = [g.unstandardize(mean_s[i, :n], var_s[i, :n])
             for i, g in enumerate(bases)]
    return reads, target.loo_moments(kinv_diag[:n])
