"""The plain reference: the same semantics as the system under test, written
independently in NumPy, at a precision the caller chooses.

It imports nothing of the program. It is handed the cell's inputs (rates,
failure schedule, simulator seeds, the configuration's constants) and,
teacher-forced, what the program decided or fitted at each step: the
reconfigurations its controllers returned, the observations its
forecasters were fed, the data and hyper-parameters of each GP fit. From
those it recomputes what the program's device path produced.

* :func:`simulate` — the cluster model (paper Sec. 3 testbed: queueing,
  consumer lag, checkpoints, timeout failures, restarts on reconfiguration)
  tick by tick for every scenario, with the Table-3 failure bookkeeping;
* :class:`Arima` — online ARIMA: RLS-tracked AR(p) on the d-differenced
  series with a forgetting factor, and its max-bin forecast (paper Sec. 2.2);
* :func:`gp_member` — an exact Matern-5/2 GP's kernel matrix at the
  program's hyper-parameters, and the backward errors of the program's
  Cholesky factor and ``K^-1 y`` against it;
* :func:`theta_gap` — how far a float64 L-BFGS, started at the program's
  hyper-parameters, still lowers the fit's objective (the negative log
  marginal likelihood with the weak log-normal priors, paper Sec. 2.2);
* :func:`ensemble_posterior` — an RGPE ensemble's posterior (paper Sec.
  2.2, eq. 1) from its members' data, hyper-parameters, factors and
  weights;
* :func:`pick` — the optimizing step's choice (paper Sec. 2.4, Fig. 4): the
  cheapest predicted-feasible configuration after the safety-buffer skip;
* :func:`ehvi` / :func:`profiling_gap` — the exact two-objective EHVI
  weighted by the probability of meeting the recovery constraint, and how
  far each profiling pick of the greedy batch (paper Sec. 2.3) falls below
  the best the reference scores in its round.

``dtype=np.float32`` gives the control: the same computation one precision
below what the configuration states.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
from scipy import linalg as sla
from scipy import optimize as sopt
from scipy import stats

def capacity(m: Dict[str, float], cfg: np.ndarray, max_par: float,
             dtype=np.float64) -> np.ndarray:
    """Sustainable events/s of ``cfg`` rows ``[S, 5]`` before noise."""
    cfg = cfg.astype(dtype)
    w, cores, mem, slots, ckpt = (cfg[:, i] for i in range(5))
    slots_total = np.minimum(w * slots, dtype(max_par))
    used = np.minimum(w, slots_total)
    per_w_slots = slots_total / np.maximum(used, dtype(1))
    mem_slot = mem / np.maximum(slots, dtype(1))
    mem_f = 1 / (1 + (dtype(m["mem_half_mb"]) / mem_slot)
                 ** dtype(m["mem_exponent"]))
    per_worker = (dtype(m["base_rate_per_core"])
                  * cores ** dtype(m["cpu_exponent"])
                  * per_w_slots ** dtype(m["slot_exponent"]) * mem_f)
    ckpt_f = 1 / (1 + dtype(m["checkpoint_cost_s"])
                  / np.maximum(ckpt, dtype(1e-3)))
    return used * per_worker * ckpt_f


def injection_ticks(times: np.ndarray, dt: float, n: int) -> List[int]:
    """Tick of each failure: the first whose time reaches it, at least one
    past the previous injection, and inside the run."""
    ticks = np.arange(n) * dt
    out: List[int] = []
    prev = -1
    for f in times:
        k = max(int(np.searchsorted(ticks, f, side="left")), prev + 1)
        if k >= n:
            break
        out.append(k)
        prev = k
    return out


@dataclass
class Record:
    """One injected failure, as Table 3 books it."""
    t_inject: float
    workload: float
    recovery_s: Optional[float] = None


@dataclass
class SimResult:
    arrays: Dict[str, np.ndarray]          # [S, n] per metric
    failures: List[List[Record]]
    n_reconfigurations: np.ndarray         # [S]


def simulate(config: Dict[str, Any], rates: np.ndarray,
             start: np.ndarray, seeds: Sequence[int],
             decisions: Sequence[Tuple[int, int, Sequence[float],
                                       Optional[float]]],
             fail_times: np.ndarray, dtype=np.float64) -> SimResult:
    """Every scenario through every tick.

    ``rates`` ``[S, n]``; ``start`` ``[S, 5]`` boot configurations;
    ``decisions`` ``(tick, scenario, config5, restart_s)``: the controller's
    reconfiguration made after ``tick`` (``restart_s`` None: the model's
    savepoint restart). A tick is: capacity noise, the job's downtime and
    checkpoint clocks, arrivals against capacity, latency noise where the
    job is up, usage; then the failures due at that tick, then the
    decisions made after it.
    """
    m = config["cluster_model"]
    dt = dtype(config["dt_s"])
    cap_s = dtype(m["latency_cap_s"])
    idle = dtype(m["cpu_idle_frac"])
    max_par = float(config["max_parallelism"])
    S, n = rates.shape
    R = rates.astype(dtype)
    cfg = start.astype(np.float64).copy()
    cap_base = capacity(m, cfg, max_par, dtype)
    # each scenario's standard-normal stream, consumed in draw order
    z = np.stack([np.random.default_rng(int(s)).standard_normal(2 * n)
                  for s in seeds]).astype(dtype)
    ptr = np.zeros(S, int)
    rows = np.arange(S)
    lag = np.zeros(S, dtype)
    down = np.zeros(S, dtype)
    since = np.zeros(S, dtype)
    last_rate = np.zeros(S, dtype)
    n_reconf = np.zeros(S, int)
    out = {k: np.zeros((S, n), dtype) for k in
           ("rates", "latencies", "usage_cpu", "usage_mem_mb",
            "consumer_lag", "workers")}
    by_tick: Dict[int, List] = {}
    for k, j, c, r in decisions:
        by_tick.setdefault(int(k), []).append((int(j), c, r))
    inject = {k: [] for k in range(n)}
    for j in range(S):
        for k in injection_ticks(fail_times, float(config["dt_s"]), n):
            inject[k].append(j)
    failures: List[List[Record]] = [[] for _ in range(S)]
    pending: Dict[int, Tuple[Record, int]] = {}
    cap_twice = 2.0 * config["guarantees"]["recovery_cap_s"]

    for k in range(n):
        r = R[:, k]
        noise = 1 + dtype(m["noise"]) * z[rows, ptr]
        ptr += 1
        cap = cap_base * np.maximum(noise, dtype(0.5))
        was_down = down > 0
        down = np.where(was_down, np.maximum(down - dt, dtype(0)), down)
        since = np.where(was_down, since, since + dt)
        ckpt = cfg[:, 4].astype(dtype)
        since = np.where(~was_down & (since >= ckpt), dtype(0), since)
        demand = r * dt + lag
        processed = np.minimum(cap * dt, demand)
        lag = np.where(was_down, lag + r * dt, demand - processed)
        util = np.minimum(r / np.maximum(cap, dtype(1e-9)), dtype(1.5))
        is_down = down > 0
        up = ~is_down
        z2 = np.zeros(S, dtype)
        z2[up] = np.abs(z[rows[up], ptr[up]])
        ptr[up] += 1
        w, cores, mem, slots = (cfg[:, i].astype(dtype) for i in range(4))
        rho = np.minimum(r / np.maximum(cap, dtype(1e-9)), dtype(0.999))
        base = dtype(m["base_latency_s"]) * (
            1 + dtype(m["queue_gamma"]) * rho / (1 - rho))
        backlog = lag / np.maximum(cap, dtype(1e-9))
        mem_slot = mem / np.maximum(slots, dtype(1))
        gc = dtype(0.25) * (dtype(1024) / mem_slot) ** 2 * rho
        lat = np.minimum((base + backlog + gc) * (1 + dtype(0.05) * z2),
                         cap_s)
        out["latencies"][:, k] = np.where(is_down, cap_s, lat)
        out["usage_cpu"][:, k] = w * cores * (
            idle + (1 - idle) * np.minimum(util, dtype(1)))
        state_mb = dtype(m["state_per_krate_mb"]) * r / 1000
        need = state_mb / np.maximum(w, dtype(1)) + 300
        frac = np.minimum(0.25 + 0.75 * need / np.maximum(mem, dtype(1)),
                          dtype(1))
        out["usage_mem_mb"][:, k] = w * mem * frac
        out["rates"][:, k] = r
        out["consumer_lag"][:, k] = lag
        out["workers"][:, k] = w
        last_rate = r
        caught = up & (lag < 1)
        t = float(k) * float(config["dt_s"])

        injected = inject[k]
        for j in injected:
            state = dtype(m["state_per_krate_mb"]) * last_rate[j] / 1000
            restore = state / (dtype(m["restore_mb_per_s"])
                               * max(w[j], dtype(1)))
            down[j] = (dtype(m["failure_detect_s"]) + dtype(m["redeploy_s"])
                       + restore)
            lag[j] += last_rate[j] * since[j]
            since[j] = 0
            if j in pending:                 # never resolved: closed open
                failures[j].append(pending[j][0])
            pending[j] = (Record(t, float(rates[j, k])), n_reconf[j])
        for j in [j for j in pending if j not in injected]:
            rec, n0 = pending[j]
            elapsed = t - rec.t_inject
            if n_reconf[j] != n0:
                rec.recovery_s = None        # NR: a reconfiguration overlapped
            elif caught[j]:
                rec.recovery_s = elapsed
            elif elapsed > cap_twice:
                rec.recovery_s = float("inf")
            else:
                continue
            failures[j].append(rec)
            del pending[j]

        for j, c, restart in by_tick.get(k, ()):
            c = np.asarray(c, np.float64)
            if np.array_equal(c, cfg[j]):
                continue
            cfg[j] = c
            cap_base[j] = capacity(m, c[None, :], max_par, dtype)[0]
            restart = m["reconfig_restart_s"] if restart is None else restart
            down[j] = max(down[j], dtype(restart))
            since[j] = 0
            n_reconf[j] += 1
    for j, (rec, _) in pending.items():
        failures[j].append(rec)
    return SimResult(out, failures, n_reconf)


# ---------------------------------------------------------------------------
# online ARIMA (paper Sec. 2.2)
# ---------------------------------------------------------------------------

class Arima:
    """AR(p) on the d-times differenced series, coefficients tracked by
    recursive least squares with forgetting; iterated rollout."""

    def __init__(self, fc: Dict[str, Any], dtype=np.float64):
        self.p, self.d = int(fc["p"]), int(fc["d"])
        self.lam = dtype(fc["forgetting"])
        self.ridge = dtype(fc["ridge"])
        self.trace_cap = float(fc["ridge"]) * (self.p + 1) * fc["p_trace_cap"]
        self.diff_cap = dtype(fc["rollout_diff_cap"])
        self.dtype = dtype
        self.hist: List[float] = []
        self.w: Optional[np.ndarray] = None
        self.P: Optional[np.ndarray] = None

    def _diff(self, s: np.ndarray) -> np.ndarray:
        for _ in range(self.d):
            s = s[1:] - s[:-1]
        return s

    def _phi(self, diffed: np.ndarray) -> np.ndarray:
        return np.concatenate([diffed[-self.p:][::-1],
                               np.ones(1, self.dtype)])

    def update(self, v: float) -> None:
        if not np.isfinite(v):
            return
        self.hist = (self.hist + [v])[-(self.p + self.d + 1):]
        if len(self.hist) < self.p + self.d + 1:
            return
        dfd = self._diff(np.asarray(self.hist, self.dtype))
        phi, target = self._phi(dfd[:-1]), dfd[-1]
        if self.w is None:
            self.w = np.zeros(self.p + 1, self.dtype)
            self.P = np.eye(self.p + 1, dtype=self.dtype) * self.ridge
        Pphi = self.P @ phi
        gain = Pphi / (self.lam + phi @ Pphi)
        err = target - self.w @ phi
        self.w = self.w + gain * err
        P = (self.P - np.outer(gain, Pphi)) / self.lam
        P = (P + P.T) * self.dtype(0.5)
        tr = float(np.trace(P))
        if tr > self.trace_cap:
            P = P * self.dtype(self.trace_cap / tr)
        self.P = P
        if not (np.isfinite(self.w).all() and np.isfinite(self.P).all()):
            self.w = np.zeros(self.p + 1, self.dtype)
            self.P = np.eye(self.p + 1, dtype=self.dtype) * self.ridge

    def forecast(self, steps: int) -> np.ndarray:
        if not self.hist:
            return np.zeros(steps, self.dtype)
        if self.w is None:
            return np.full(steps, self.hist[-1], self.dtype)
        s = np.asarray(self.hist, self.dtype)
        dfd = list(self._diff(s))
        tails = []
        x = s
        for _ in range(self.d):
            tails.append(x[-1])
            x = x[1:] - x[:-1]
        lim = self.diff_cap * max(self.dtype(1),
                                  np.max(np.abs(np.asarray(dfd[-self.p:],
                                                           self.dtype))))
        out = []
        for _ in range(steps):
            nxt = np.clip(self.w @ self._phi(np.asarray(dfd, self.dtype)),
                          -lim, lim)
            dfd = (dfd + [nxt])[-self.p:]
            v = nxt
            for j in range(self.d - 1, -1, -1):
                v = v + tails[j]
                tails[j] = v
            out.append(v)
        return np.asarray(out, self.dtype)


def max_bin(fc: np.ndarray, bins: int) -> float:
    """Paper Sec. 2.2: the highest of the horizon's bin averages (>= 0)."""
    pos = np.maximum(fc, 0)
    return float(pos.reshape(bins, -1).mean(axis=1).max())


# ---------------------------------------------------------------------------
# exact GP (paper Sec. 2.2)
# ---------------------------------------------------------------------------

def _matern52(x: np.ndarray, ls: np.ndarray, signal, dtype) -> np.ndarray:
    z = x / ls
    d2 = np.sum((z[:, None, :] - z[None, :, :]) ** 2, axis=-1)
    d2 = np.maximum(d2, dtype(1e-12))
    r = np.sqrt(d2)
    s5r = np.sqrt(dtype(5)) * r
    return signal * (1 + s5r + dtype(5) * d2 / 3) * np.exp(-s5r)


def kernel_matrix(x: np.ndarray, theta: np.ndarray, jitter: float,
                  dtype=np.float64) -> np.ndarray:
    """Matern-5/2 ARD kernel matrix plus noise and jitter at ``theta``
    (``d`` log lengthscales, log signal, log noise)."""
    x, theta = np.asarray(x, dtype), np.asarray(theta, dtype)
    n, d = x.shape
    ls, signal, noise = np.exp(theta[:d]), np.exp(theta[d]), \
        np.exp(theta[d + 1])
    return _matern52(x, ls, signal, dtype) \
        + (noise + dtype(jitter)) * np.eye(n, dtype=dtype)


def gp_member(x: np.ndarray, y: np.ndarray, theta: np.ndarray,
              chol: np.ndarray, alpha: np.ndarray, jitter: float
              ) -> Tuple[float, float]:
    """``(chol_err, alpha_err)`` of a fit at its hyper-parameters ``theta``:

    * ``chol_err`` = ``|L L^T - K|_F / |K|_F``, the backward error of the
      program's Cholesky factor ``L`` of the kernel matrix ``K``;
    * ``alpha_err`` = ``|K a - y| / (|K|_2 |a| + |y|)``, the backward error
      of the program's ``a = K^-1 y``.
    """
    K = kernel_matrix(x, theta, jitter)
    L = np.asarray(chol, np.float64)
    a = np.asarray(alpha, np.float64)
    y = np.asarray(y, np.float64)
    chol_err = np.linalg.norm(L @ L.T - K) / np.linalg.norm(K)
    alpha_err = np.linalg.norm(K @ a - y) / (
        np.linalg.norm(K, 2) * np.linalg.norm(a) + np.linalg.norm(y))
    return float(chol_err), float(alpha_err)


#: the fit objective's weak log-normal priors: (mean, variance) of the log
#: lengthscales, the log signal and the log noise
PRIORS = ((np.log(0.5), 4.0), (0.0, 4.0), (np.log(1e-2), 9.0))


def neg_log_evidence(theta: np.ndarray, x: np.ndarray, y: np.ndarray,
                     jitter: float) -> Tuple[float, np.ndarray]:
    """The fit objective and its gradient at ``theta`` in float64: the
    negative log marginal likelihood of the standardized targets ``y`` plus
    the priors; ``inf`` where the kernel matrix is not positive definite."""
    n, d = x.shape
    ls, signal, noise = np.exp(theta[:d]), np.exp(theta[d]), \
        np.exp(theta[d + 1])
    diff = (x[:, None, :] - x[None, :, :]) / ls
    d2 = np.maximum(np.sum(diff * diff, -1), 1e-12)
    s5r = np.sqrt(5.0) * np.sqrt(d2)
    e = np.exp(-s5r)
    km = signal * (1 + s5r + 5.0 * d2 / 3) * e
    K = km + (noise + jitter) * np.eye(n)
    try:
        L = np.linalg.cholesky(K)
    except np.linalg.LinAlgError:
        return float("inf"), np.zeros_like(theta)
    a = sla.cho_solve((L, True), y)
    (m_ls, v_ls), (m_s, v_s), (m_n, v_n) = PRIORS
    prior = (np.sum((theta[:d] - m_ls) ** 2) / (2 * v_ls)
             + (theta[d] - m_s) ** 2 / (2 * v_s)
             + (theta[d + 1] - m_n) ** 2 / (2 * v_n))
    f = 0.5 * y @ a + np.sum(np.log(np.diag(L))) \
        + 0.5 * n * np.log(2 * np.pi) + prior
    W = 0.5 * (sla.cho_solve((L, True), np.eye(n)) - np.outer(a, a))
    dk = signal * (5.0 / 3) * (1 + s5r) * e
    g = np.empty_like(theta)
    g[:d] = np.einsum("ij,ij,ijk->k", W, dk, diff * diff) \
        + (theta[:d] - m_ls) / v_ls
    g[d] = np.sum(W * km) + (theta[d] - m_s) / v_s
    g[d + 1] = np.trace(W) * noise + (theta[d + 1] - m_n) / v_n
    return float(f), g


def theta_gap(x: np.ndarray, y: np.ndarray, theta: np.ndarray,
              jitter: float, max_iter: int = 200) -> float:
    """Nats by which a float64 L-BFGS, started at ``theta``, lowers the
    objective: about 0 at an optimum, ``inf`` where the objective at
    ``theta`` is not finite."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    t0 = np.asarray(theta, np.float64)
    f0, _ = neg_log_evidence(t0, x, y, jitter)
    if not np.isfinite(f0):
        return float("inf")
    res = sopt.minimize(neg_log_evidence, t0, args=(x, y, jitter),
                        jac=True, method="L-BFGS-B",
                        options={"maxiter": max_iter, "gtol": 1e-10,
                                 "ftol": 1e-15})
    return max(f0 - float(res.fun), 0.0)


def _matern52_cross(xq: np.ndarray, x: np.ndarray, ls: np.ndarray,
                    signal: float) -> np.ndarray:
    diff = (xq[:, None, :] - x[None, :, :]) / ls
    d2 = np.maximum(np.sum(diff * diff, -1), 1e-12)
    s5r = np.sqrt(5.0) * np.sqrt(d2)
    return signal * (1 + s5r + 5.0 * d2 / 3) * np.exp(-s5r)


def ensemble_posterior(xq: np.ndarray, members: Sequence[Tuple],
                       weights: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray]:
    """``(mean, var, mean_scale, var_scale)`` of an ensemble at ``xq`` in
    float64. ``members``: ``(x, theta, chol, alpha, y_mean, y_std)`` with
    the program's factor and ``K^-1 y``; each member's mean is
    ``k(xq, x) a`` and its variance ``signal - |L^-1 k(x, xq)|^2``
    (floored at 1e-10), both in original units; the ensemble's are
    ``sum w_i mean_i`` and ``sum w_i^2 var_i`` (floored at 1e-12). The
    scales bound what rounding can move each by: ``sum |w_i| (y_std_i
    sum_j |k_ij a_j| + |y_mean_i|)`` and ``sum w_i^2 y_std_i^2 (signal_i +
    |L^-1 k|^2)``."""
    xq = np.atleast_2d(np.asarray(xq, np.float64))
    mean = np.zeros(len(xq))
    var = np.zeros(len(xq))
    m_scale = np.zeros(len(xq))
    v_scale = np.zeros(len(xq))
    for (x, theta, chol, alpha, y_mean, y_std), w in zip(members, weights):
        x = np.asarray(x, np.float64)
        theta = np.asarray(theta, np.float64)
        d = x.shape[1]
        signal = float(np.exp(theta[d]))
        ks = _matern52_cross(xq, x, np.exp(theta[:d]), signal)
        a = np.asarray(alpha, np.float64)
        v = sla.solve_triangular(np.asarray(chol, np.float64), ks.T,
                                 lower=True)
        vv = np.sum(v * v, axis=0)
        mean += w * (ks @ a * y_std + y_mean)
        var += w * w * np.maximum(signal - vv, 1e-10) * y_std ** 2
        m_scale += abs(w) * (y_std * (np.abs(ks) @ np.abs(a)) + abs(y_mean))
        v_scale += w * w * y_std ** 2 * (signal + vv)
    return mean, np.maximum(var, 1e-12), m_scale, v_scale


def pick(mu_usage: np.ndarray, mu_latency: np.ndarray,
         mu_recovery: Optional[np.ndarray], lc: float, rc: float,
         safety_buffer: float) -> Optional[int]:
    """Paper Sec. 2.4: among the candidates predicted to meet the latency
    constraint (and the recovery constraint, where modelled), sorted by
    predicted usage, the one a ``safety_buffer`` share up from the
    cheapest; None where none is feasible."""
    feasible = mu_latency < lc
    if mu_recovery is not None:
        feasible &= mu_recovery <= rc
    idx = np.flatnonzero(feasible)
    if len(idx) == 0:
        return None
    order = idx[np.argsort(mu_usage[idx])]
    k = min(int(np.floor(safety_buffer * len(order))), len(order) - 1)
    return int(order[k])


def _ramp(c: np.ndarray, mu: np.ndarray, sd: np.ndarray) -> np.ndarray:
    """E[max(c - Z, 0)], Z ~ N(mu, sd^2); 0 where c = -inf."""
    sd = np.maximum(sd, 1e-12)
    neg_inf = np.isneginf(c)
    c0 = np.where(neg_inf, 0.0, c)
    z = (c0 - mu) / sd
    out = (c0 - mu) * stats.norm.cdf(z) + sd * stats.norm.pdf(z)
    return np.where(neg_inf, 0.0, out)


def pareto_front(points: np.ndarray) -> np.ndarray:
    """Non-dominated points (both objectives minimized), sorted by the
    first."""
    points = np.asarray(points, np.float64).reshape(-1, 2)
    front, best = [], np.inf
    for p in points[np.lexsort((points[:, 1], points[:, 0]))]:
        if p[1] < best - 1e-15:
            front.append(p)
            best = p[1]
    return np.asarray(front).reshape(-1, 2)


def ehvi(mu: np.ndarray, var: np.ndarray, front: np.ndarray,
         ref: Tuple[float, float]) -> np.ndarray:
    """Exact expected hypervolume improvement of each candidate over the
    observed front, under independent Gaussian marginals ``mu``/``var``
    ``[n, 2]``: the dominated region split into strips along the first
    objective."""
    sd = np.sqrt(np.maximum(var, 1e-18))
    f = pareto_front(front)
    f = f[(f[:, 0] < ref[0]) & (f[:, 1] < ref[1])]
    edges = np.concatenate([[-np.inf], f[:, 0], [ref[0]]])
    heights = np.concatenate([[ref[1]], f[:, 1]])
    right = _ramp(np.minimum(edges[1:], ref[0])[None, :], mu[:, :1],
                  sd[:, :1])
    left = _ramp(edges[:-1][None, :], mu[:, :1], sd[:, :1])
    widths = np.maximum(right - left, 0.0)
    return np.sum(widths * _ramp(heights[None, :], mu[:, 1:], sd[:, 1:]),
                  axis=1)


#: EHVI, as a share of the reference box, below which a score is rounding
TINY_EHVI = 1e-9


def profiling_scores(rec: Dict[str, Any], front: np.ndarray) -> np.ndarray:
    """Feasibility-weighted EHVI of every candidate against ``front``."""
    score = ehvi(np.asarray(rec["mu"], np.float64),
                 np.asarray(rec["var"], np.float64), front, rec["ref"])
    if rec.get("rmu") is not None and rec["rc"] is not None:
        sd = np.sqrt(np.maximum(np.asarray(rec["rvar"], np.float64), 1e-18))
        score = score * stats.norm.cdf(
            (rec["rc"] - np.asarray(rec["rmu"], np.float64)) / sd)
    if rec["bias"] is not None:
        score = score * rec["bias"]
    return score


def profiling_gap(rec: Dict[str, Any], picked: Sequence[int]) -> float:
    """Widest share by which a profiling pick's score falls below the best
    score of its round, each round's front holding the picks before it at
    their posterior means (Kriging believer): 0 where every pick is the
    reference's best; 1 where a pick is one the reference rules out. A
    batch that stops while the reference still scores a candidate above
    ``TINY_EHVI`` of the reference box reads that score as a share of the
    first round's best; below it a score is rounding, and a pick or a stop
    there reads 0."""
    mu = np.asarray(rec["mu"], np.float64)
    dead = np.zeros(len(mu), bool)
    dead[list(rec["exclude"])] = True
    front = np.asarray(rec["front"], np.float64).reshape(-1, 2)
    tiny = TINY_EHVI * abs(rec["ref"][0] * rec["ref"][1])
    if len(picked) > rec["q"] or len(set(picked)) < len(picked):
        return 1.0
    worst, first = 0.0, None
    for r in range(rec["q"]):
        score = profiling_scores(rec, front)
        score[dead] = -np.inf
        best = float(np.max(score))
        first = max(best, tiny) if first is None else first
        if r >= len(picked):
            if best > tiny:
                worst = max(worst, best / first)
            break
        j = int(picked[r])
        if dead[j]:
            return 1.0
        if best > tiny:
            worst = max(worst, (best - float(score[j])) / best)
        dead[j] = True
        front = np.vstack([front, mu[j]])
        if dead.all():
            break
    return worst
