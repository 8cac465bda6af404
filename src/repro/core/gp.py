"""Exact Gaussian-process regression (the unit model behind MOBO, paper §2.2).

One GP per (segment, objective/constraint). Matérn-5/2 kernel with ARD
lengthscales; inputs live in the unit hypercube (see
:mod:`repro.core.config_space`); targets are standardized internally so the
weak log-normal hyper-priors are scale-free.

This module is the **scalar reference oracle**: :meth:`GP.fit` optimizes the
marginal log likelihood with multi-restart scipy L-BFGS-B driving a jax
value-and-grad, one model at a time. The production hot path is
:mod:`repro.core.gp_bank`, which fits whole segment x objective x scenario
batches of these GPs in a single vmapped, jitted L-BFGS dispatch from the
same restart initializations and the same objective — the two paths are
pinned against each other in ``tests/test_gp_bank.py``. The kernel,
hyper-parameter packing (``theta`` = d log-lengthscales, log signal, log
noise) and priors below are shared by both.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from scipy import optimize as sopt

from .. import obs

_JITTER = 1e-6

#: Every GP matmul asks for full float32. At the default precision a TPU
#: multiplies float32 in one bfloat16 pass (~3 digits): the expanded
#: squared distance below cancels, the kernel matrix loses definiteness,
#: and fits become so sensitive that 1e-12 changes in the training data
#: change the fitted model. CPUs compute float32 either way.
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(a, b, precision=HIGHEST)


# --------------------------------------------------------------------------
# kernel + marginal likelihood (pure functions of log-hyper-parameters)
# --------------------------------------------------------------------------
def _matern52(x1: jnp.ndarray, x2: jnp.ndarray, ls: jnp.ndarray,
              signal: jnp.ndarray) -> jnp.ndarray:
    """Matérn-5/2 with ARD lengthscales. x1: (n,d), x2: (m,d) -> (n,m)."""
    z1 = x1 / ls
    z2 = x2 / ls
    d2 = jnp.sum(z1 * z1, -1)[:, None] + jnp.sum(z2 * z2, -1)[None, :] \
        - 2.0 * _mm(z1, z2.T)
    r = jnp.sqrt(jnp.maximum(d2, 1e-12))
    s5r = jnp.sqrt(5.0) * r
    return signal * (1.0 + s5r + 5.0 * d2 / 3.0) * jnp.exp(-s5r)


def _unpack(theta: jnp.ndarray, dim: int):
    ls = jnp.exp(theta[:dim])
    signal = jnp.exp(theta[dim])
    noise = jnp.exp(theta[dim + 1])
    return ls, signal, noise


def _neg_mll(theta: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    n, dim = x.shape
    ls, signal, noise = _unpack(theta, dim)
    k = _matern52(x, x, ls, signal) + (noise + _JITTER) * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    mll = (-0.5 * _mm(y, alpha)
           - jnp.sum(jnp.log(jnp.diagonal(chol)))
           - 0.5 * n * jnp.log(2.0 * jnp.pi))
    # Weak log-normal priors keep hyper-parameters in a sane band when n is
    # tiny (the cold-start regime RGPE is designed for).
    prior = (jnp.sum((theta[:dim] - jnp.log(0.5)) ** 2) / 8.0
             + (theta[dim]) ** 2 / 8.0
             + (theta[dim + 1] - jnp.log(1e-2)) ** 2 / 18.0)
    return -(mll - prior)


_neg_mll_grad = jax.value_and_grad(_neg_mll)


def restart_inits(dim: int, restarts: int, seed: int) -> np.ndarray:
    """Multi-restart starting points for the log hyper-parameters, (R, d+2).

    Single source of truth for both optimizers: the scalar scipy path below
    and the batched path (:meth:`repro.core.gp_bank.GPBank.fit`) must draw
    identical initializations for their fits to agree.
    """
    rng = np.random.default_rng(seed)
    t0s = np.empty((max(restarts, 1), dim + 2))
    for r in range(max(restarts, 1)):
        t0s[r] = np.concatenate([
            np.log(rng.uniform(0.2, 1.0, dim)),
            [np.log(rng.uniform(0.5, 2.0))],
            [np.log(rng.uniform(1e-3, 1e-1))],
        ])
    return t0s


def draw_marginals(mean: np.ndarray, var: np.ndarray, n_samples: int,
                   rng: np.random.Generator) -> np.ndarray:
    """Independent normal draws at each of m marginals, (n_samples, m)."""
    return rng.normal(mean[None, :], np.sqrt(var)[None, :],
                      size=(n_samples, len(mean)))


@dataclass
class GP:
    """A fitted exact GP.

    Construct via :meth:`GP.fit` (scalar scipy path) or slice one out of a
    fitted :class:`~repro.core.gp_bank.GPBank` with
    :meth:`~repro.core.gp_bank.GPBank.member`; both produce this same
    dataclass, so downstream consumers (RGPE, the controller) never care
    which optimizer fitted the model.
    """

    x: np.ndarray            # (n, d) unit-cube inputs
    y_mean: float
    y_std: float
    theta: np.ndarray        # log hyper-parameters (d lengthscales, signal, noise)
    chol: np.ndarray         # Cholesky of K + noise I
    alpha: np.ndarray        # K^-1 y (standardized)

    # -- fitting -----------------------------------------------------------
    @staticmethod
    def fit(x: np.ndarray, y: np.ndarray, *, restarts: int = 3,
            seed: int = 0, max_iter: int = 120) -> "GP":
        x = np.asarray(x, np.float64).reshape(len(y), -1)
        y = np.asarray(y, np.float64).ravel()
        n, dim = x.shape
        y_mean = float(y.mean())
        y_std = float(y.std()) or 1.0
        ys = (y - y_mean) / y_std

        xj, yj = jnp.asarray(x), jnp.asarray(ys)

        def objective(t64: np.ndarray) -> Tuple[float, np.ndarray]:
            v, g = _neg_mll_grad(jnp.asarray(t64), xj, yj)
            return float(v), np.asarray(g, np.float64)

        best_v, best_t = np.inf, None
        for t0 in restart_inits(dim, restarts, seed):
            res = sopt.minimize(objective, t0, jac=True, method="L-BFGS-B",
                                options={"maxiter": max_iter})
            if res.fun < best_v and np.isfinite(res.fun):
                best_v, best_t = float(res.fun), np.asarray(res.x)
        if best_t is None:  # pragma: no cover - L-BFGS never totally fails here
            best_t = np.concatenate([np.zeros(dim), [0.0], [np.log(1e-2)]])

        ls, signal, noise = _unpack(jnp.asarray(best_t), dim)
        k = _matern52(xj, xj, ls, signal) + (noise + _JITTER) * jnp.eye(n)
        chol = np.asarray(jnp.linalg.cholesky(k))
        alpha = np.asarray(jax.scipy.linalg.cho_solve((jnp.asarray(chol), True), yj))
        return GP(x=x, y_mean=y_mean, y_std=y_std, theta=np.asarray(best_t),
                  chol=chol, alpha=alpha)

    # -- posterior ---------------------------------------------------------
    def posterior(self, xq: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Posterior mean and variance (original units) at (m, d) queries."""
        obs.inc("gp.single_reads")
        xq = np.asarray(xq, np.float64).reshape(-1, self.x.shape[1])
        dim = self.x.shape[1]
        ls, signal, noise = _unpack(jnp.asarray(self.theta), dim)
        ks = _matern52(jnp.asarray(xq), jnp.asarray(self.x), ls, signal)
        mean_s = _mm(ks, jnp.asarray(self.alpha))
        v = jax.scipy.linalg.solve_triangular(jnp.asarray(self.chol), ks.T,
                                              lower=True)
        var_s = jnp.maximum(signal - jnp.sum(v * v, axis=0), 1e-10)
        return self.unstandardize(np.asarray(mean_s), np.asarray(var_s))

    def unstandardize(self, mean_s: np.ndarray, var_s: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Standardized posterior mean and variance in original units."""
        return mean_s * self.y_std + self.y_mean, var_s * self.y_std ** 2

    def sample(self, xq: np.ndarray, n_samples: int,
               rng: np.random.Generator) -> np.ndarray:
        """Independent-marginal posterior samples, (n_samples, m)."""
        return draw_marginals(*self.posterior(xq), n_samples, rng)

    def loo_samples(self, n_samples: int, rng: np.random.Generator) -> np.ndarray:
        """Leave-one-out posterior samples at the training points.

        Used by RGPE to score the target model without optimistic bias
        (Feurer et al.). Uses the closed-form LOO identities on K^-1.
        """
        obs.inc("gp.single_reads")
        n, dim = self.x.shape
        ls, signal, noise = _unpack(jnp.asarray(self.theta), dim)
        k = _matern52(jnp.asarray(self.x), jnp.asarray(self.x), ls, signal) \
            + (noise + _JITTER) * jnp.eye(n)
        kinv = np.asarray(jnp.linalg.inv(k))
        return self.loo_draws(*self.loo_moments(np.diag(kinv)), n_samples,
                              rng)

    def loo_moments(self, kinv_diag: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Standardized LOO mean and variance at the training points from
        the diagonal of ``(K + (noise + jitter) I)^-1``."""
        ys = (self.chol @ self.chol.T) @ self.alpha  # K alpha = standardized y
        return (ys - self.alpha / kinv_diag,
                np.maximum(1.0 / kinv_diag, 1e-10))

    def loo_draws(self, mu_loo: np.ndarray, var_loo: np.ndarray,
                  n_samples: int, rng: np.random.Generator) -> np.ndarray:
        """LOO samples (original units) from :meth:`loo_moments`."""
        s = draw_marginals(mu_loo, var_loo, n_samples, rng)
        return s * self.y_std + self.y_mean

    @property
    def train_targets(self) -> np.ndarray:
        ys = (self.chol @ self.chol.T) @ self.alpha  # K alpha = standardized y
        return ys * self.y_std + self.y_mean
