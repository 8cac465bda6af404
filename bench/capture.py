"""The benchmark's wrappers around calls into the program's layers.

They record, for the sweep the window ends on, what the reference is
teacher-forced with and what it is compared with: boot configurations,
reconfigurations, forecaster observations and reads, GP fits (with their
restart starts), the ensemble posteriors the Demeter controllers read, the
configuration each optimizing step picked from them and each profiling
batch they chose; and they time the engine's interval step. Each wrapper
calls the original and returns its result unchanged.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


class Capture:
    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.start_configs: List[Tuple[float, ...]] = []
        #: (tick, scenario, config5, restart_s, applied)
        self.decisions: List[Tuple[int, int, Tuple[float, ...], Any,
                                   bool]] = []
        #: forecaster row -> observations in the order they were fed
        self.fc_updates: Dict[int, List[float]] = defaultdict(list)
        #: (row, observations fed so far, max-bin forecast read)
        self.fc_reads: List[Tuple[int, int, float]] = []
        #: per GPBank fit dispatch: (x, y, mask, theta, value, chol, alpha,
        #: t0s, max_iter)
        self.gp_fits: List[Tuple[Any, ...]] = []
        #: per ensemble posterior read: members (x, theta, chol, alpha,
        #: y_mean, y_std) with weight > 0, their weights, the queries and
        #: the mean and variance returned
        self.posts: List[Dict[str, Any]] = []
        #: per optimizing-step pick: latency constraint, recovery
        #: constraint, safety buffer, the posterior reads it consumed
        #: (indices into ``posts``: usage, latency[, recovery]) and the
        #: candidate index it returned (None: no pick)
        self.picks: List[Dict[str, Any]] = []
        #: per optimizing step: the observed usage it was handed, the
        #: running configuration's candidate index, the picks it made
        #: (indices into ``picks``), the efficiency threshold, and what it
        #: returned (a candidate index, -2 outside the candidates, None: no
        #: change) and whether that is C_max
        self.opt_steps: List[Dict[str, Any]] = []
        #: per profiling-batch selection: the posterior marginals, observed
        #: front, reference point, q, constraints, bias and the picks
        self.profiles: List[Dict[str, Any]] = []
        self.step_interval_s = 0.0
        self.intervals = 0


_FIELDS = ("workers", "cpu_cores", "memory_mb", "task_slots",
           "checkpoint_interval_s")


def _index(ctl, cfg) -> int:
    """``cfg``'s position among the controller's candidates (-2: none)."""
    try:
        return ctl.space.index(cfg)
    except (KeyError, TypeError):
        return -2


def _row(cfg) -> Tuple[float, ...]:
    return tuple(float(getattr(cfg, f)) for f in _FIELDS)


def install(cap: Capture, annotate: bool = False) -> None:
    """Wrap the program's seams in this process (idempotent per process:
    the wrappers read ``cap`` through a module-level slot)."""
    global _CAP, _ANNOTATE
    _CAP, _ANNOTATE = cap, annotate
    if _INSTALLED:
        return
    _install()


_CAP: Capture = Capture()
_ANNOTATE = False
#: posterior reads of the pick being made, or None outside a pick
_PICK: List[Optional[List[int]]] = [None]
_INSTALLED = False
#: the program's own functions the wrappers call (a test may swap one for
#: a broken version underneath the capture)
ORIGINAL: Dict[str, Any] = {}


def _install() -> None:
    global _INSTALLED
    from jax.profiler import TraceAnnotation
    from repro.core import demeter, gp_bank
    from repro.core.forecast_bank import BankedForecaster
    from repro.core.rgpe import RGPEnsemble
    from repro.dsp.fused import FusedSweepExecutor

    init0 = FusedSweepExecutor.__init__
    step0 = FusedSweepExecutor.step_interval
    reconf0 = FusedSweepExecutor.reconfigure_one
    upd0 = BankedForecaster.update
    binned0 = BankedForecaster.binned
    ORIGINAL["fit_packed"] = gp_bank._fit_packed
    ORIGINAL["posterior"] = RGPEnsemble.posterior
    ORIGINAL["pick_config"] = demeter.DemeterController._pick_config
    ORIGINAL["select_profiling_batch"] = demeter.select_profiling_batch
    ORIGINAL["optimization_step"] = \
        demeter.DemeterController.optimization_step

    def init(self, model, configs, seeds, **kw):
        _CAP.start_configs = [_row(c) for c in configs]
        init0(self, model, configs, seeds, **kw)

    def step_interval(self, rates_ks, inject_ks=None):
        t0 = time.perf_counter()
        if _ANNOTATE:
            with TraceAnnotation("bench.step_interval"):
                out = step0(self, rates_ks, inject_ks)
        else:
            out = step0(self, rates_ks, inject_ks)
        _CAP.step_interval_s += time.perf_counter() - t0
        _CAP.intervals += 1
        return out

    def reconfigure_one(self, idx, cfg, restart_s=None):
        tick = int(self.step_index)
        applied = reconf0(self, idx, cfg, restart_s)
        _CAP.decisions.append((tick, int(idx), _row(cfg), restart_s,
                               bool(applied)))
        return applied

    def update(self, value):
        _CAP.fc_updates[self.row].append(float(value))
        return upd0(self, value)

    def binned(self, horizon, bins):
        out = binned0(self, horizon, bins)
        _CAP.fc_reads.append((self.row, len(_CAP.fc_updates[self.row]),
                              float(out)))
        return out

    def fit_packed(x, y, mask, t0s, max_iter):
        theta, val, chol, alpha = ORIGINAL["fit_packed"](
            x, y, mask, t0s, max_iter=max_iter)
        # device arrays: read back after the window
        _CAP.gp_fits.append((x, y, mask, theta, val, chol, alpha, t0s,
                             max_iter))
        return theta, val, chol, alpha

    def posterior(self, xq):
        mean, var = ORIGINAL["posterior"](self, xq)
        active = [(g, a) for g, a in zip(self.gps, self.weights) if a > 0.0]
        _CAP.posts.append({
            "members": [(g.x, g.theta, g.chol, g.alpha, g.y_mean, g.y_std)
                        for g, _ in active],
            "weights": np.asarray([a for _, a in active], float),
            "xq": xq, "mean": np.asarray(mean), "var": np.asarray(var)})
        if _PICK[0] is not None:
            _PICK[0].append(len(_CAP.posts) - 1)
        return mean, var

    def pick_config(self, segment):
        outer, _PICK[0] = _PICK[0], []
        try:
            out = ORIGINAL["pick_config"](self, segment)
            reads = _PICK[0]
        finally:
            _PICK[0] = outer
        j = None if out is None else _index(self, out[0])
        _CAP.picks.append({
            "lc": self.lc.constraint(),
            "rc": float(self.hp.recovery_constraint_s),
            "sb": float(self.hp.safety_buffer), "reads": reads,
            "choice": j,
            "usage": None if out is None else float(out[1])})
        return out

    def optimization_step(self, metrics=None):
        first = len(_CAP.picks)
        current = self.executor.current_config()
        cmax = self.executor.cmax_config()
        out = ORIGINAL["optimization_step"](self, metrics)
        _CAP.opt_steps.append({
            "usage": float((metrics or {}).get("usage", float("nan"))),
            "current": _index(self, current),
            "picks": list(range(first, len(_CAP.picks))),
            "et": float(self.hp.efficiency_threshold),
            "returned": None if out is None else _index(self, out),
            "cmax": out is not None and out == cmax})
        return out

    def select_profiling_batch(candidates, post_objectives, post_recovery,
                               observed_front, ref, q, **kw):
        rec: Dict[str, Any] = {}

        def objectives(xq):
            rec["mu"], rec["var"] = post_objectives(xq)
            return rec["mu"], rec["var"]

        def recovery(xq):
            rec["rmu"], rec["rvar"] = post_recovery(xq)
            return rec["rmu"], rec["rvar"]

        picked = ORIGINAL["select_profiling_batch"](
            candidates, objectives,
            None if post_recovery is None else recovery,
            observed_front, ref, q, **kw)
        bias = kw.get("bias")
        _CAP.profiles.append({
            **rec, "front": np.asarray(observed_front, float).reshape(-1, 2),
            "ref": tuple(float(r) for r in ref), "q": int(q),
            "rc": kw.get("recovery_constraint"),
            "exclude": list(kw.get("exclude", ())),
            "bias": None if bias is None else np.asarray(bias, float),
            "picked": [int(j) for j in picked]})
        return picked

    fit_packed._cache_size = gp_bank._fit_packed._cache_size  # jit probe
    FusedSweepExecutor.__init__ = init
    FusedSweepExecutor.step_interval = step_interval
    FusedSweepExecutor.reconfigure_one = reconfigure_one
    BankedForecaster.update = update
    BankedForecaster.binned = binned
    gp_bank._fit_packed = fit_packed
    RGPEnsemble.posterior = posterior
    demeter.DemeterController._pick_config = pick_config
    demeter.select_profiling_batch = select_profiling_batch
    demeter.DemeterController.optimization_step = optimization_step
    _INSTALLED = True
