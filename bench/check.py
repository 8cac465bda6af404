"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (:mod:`bench.reference`), number by number,
each against its limit in ``bench/limits.json``.

Numbers (a cell reports those its traffic exercises):

* ``sim_rel_err`` — the widest relative gap, over every scenario, tick and
  telemetry array (rates, latencies, CPU and memory usage, workers,
  consumer lag), between the engine's result and the reference replay of
  the same scenario under the same controller decisions;
* ``table3_mismatched`` — scenarios whose failure records (injection time,
  load, recovery seconds, NR, 6m+) or reconfiguration count differ from the
  reference's bookkeeping (exact);
* ``forecast_rel_err`` — the widest relative gap between a max-bin
  forecast the controllers read from the forecast bank and the reference
  forecaster fed the same observations;
* ``gp_chol_err`` / ``gp_alpha_err`` — the widest backward error of a GP
  fit's Cholesky factor / ``K^-1 y`` against the reference kernel matrix at
  the fit's hyper-parameters; a fit whose factor or ``K^-1 y`` is not
  finite reads ``inf``;
* ``gp_theta_gap`` — the most nats by which a float64 L-BFGS started at a
  fit's hyper-parameters still lowers its objective (a fit stopped short
  of its optimum, or led by a wrong gradient, reads high);
* ``gp_fits_unchecked`` — GP fits the sweep counted that the comparison
  did not see (exact: every fit is checked);
* ``gp_mean_err`` / ``gp_var_err`` — the widest gap, as a share of what
  rounding can move it by, between an ensemble posterior mean / variance
  the controllers read and the reference's from the same members, weights
  and factors;
* ``picks_mismatched`` — optimizing steps whose picked configuration (or
  its predicted usage) differs from the reference's pick on the same
  posterior means (exact);
* ``opt_steps_mismatched`` — optimizing steps whose outcome does not follow
  from their pick: a change to a configuration other than C_max that is
  not the step's pick, or whose predicted saving on the observed usage is
  under the efficiency threshold, or a pick that would save that much and
  was not applied (exact);
* ``profile_pick_gap`` — the widest share by which a profiling pick's
  feasibility-weighted EHVI falls below the reference's best in its round.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List, Tuple

import numpy as np

from . import reference as ref

LIMITS = json.loads((Path(__file__).resolve().parent
                     / "limits.json").read_text())

#: |b| below which a telemetry element's gap is taken relative to this
REL_FLOOR = 1e-6
ARRAYS = ("rates", "latencies", "usage_cpu", "usage_mem_mb", "workers",
          "consumer_lag")


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    """max |a - b| / max(|b|, REL_FLOOR); NaN on one side only is inf."""
    a = np.asarray(a, float)
    b = np.asarray(b, float)
    if a.shape != b.shape:
        return float("inf")
    d = np.abs(a - b) / np.maximum(np.abs(b), REL_FLOOR)
    d = np.where(np.isnan(a) & np.isnan(b), 0.0, d)
    d = np.where(np.isnan(d), np.inf, d)
    return float(d.max()) if d.size else 0.0


def replay_inputs(cap, meta) -> Dict[str, Any]:
    return {"start": np.asarray(cap.start_configs, float)[:len(
                meta["sim_seeds"])],
            "decisions": [(k, j, c, r) for k, j, c, r, _ in cap.decisions]}


def sim_numbers(config: Dict[str, Any], meta: Dict[str, Any], result,
                sim: "ref.SimResult") -> Tuple[Dict[str, float], List[str]]:
    """The engine's scenarios against a reference replay ``sim``."""
    worst = 0.0
    bad: List[str] = []
    for j, sc in enumerate(result.scenarios):
        e = max(rel_err(getattr(sc, k), sim.arrays[k][j]) for k in ARRAYS)
        worst = max(worst, e)
        mine = [(f.t_inject, f.workload, f.recovery_s) for f in sc.failures]
        theirs = [(f.t_inject, f.workload, f.recovery_s)
                  for f in sim.failures[j]]
        if (sc.n_reconfigurations != int(sim.n_reconfigurations[j])
                or not _same_records(mine, theirs)):
            bad.append(sc.name)
    return {"sim_rel_err": worst, "table3_mismatched": float(len(bad))}, bad


def _same_records(a, b) -> bool:
    if len(a) != len(b):
        return False
    for (ta, wa, ra), (tb, wb, rb) in zip(a, b):
        if ta != tb or wa != wb or (ra is None) != (rb is None):
            return False
        if ra is not None and not (ra == rb or np.isclose(ra, rb)):
            return False
    return True


def forecast_number(config: Dict[str, Any], cap,
                    dtype=np.float64) -> float:
    """Widest gap of the forecasts the controllers read."""
    fc = config["forecaster"]
    by_row: Dict[int, List[Tuple[int, float]]] = {}
    for row, n, val in cap.fc_reads:
        by_row.setdefault(row, []).append((n, val))
    worst = 0.0
    for row, reads in by_row.items():
        model = ref.Arima(fc, dtype)
        obs = cap.fc_updates[row]
        fed = 0
        for n, val in sorted(reads, key=lambda r: r[0]):
            while fed < n:
                model.update(obs[fed])
                fed += 1
            want = ref.max_bin(model.forecast(fc["horizon"]), fc["bins"])
            worst = max(worst, abs(val - want) / max(abs(want), 1.0))
    return worst


def gp_members(cap):
    """Every real member of every captured fit dispatch, read back:
    ``(x, y, theta, chol, alpha)``; padding members (one point at the
    origin) are left out."""
    out = []
    for fit in cap.gp_fits:
        x, y, mask, theta, _val, chol, alpha = (np.asarray(a)
                                                for a in fit[:7])
        for i in range(len(mask)):
            n = int(mask[i].sum())
            if n < 2:
                continue
            out.append((x[i, :n], y[i, :n], theta[i], chol[i, :n, :n],
                        alpha[i, :n]))
    return out


def gp_numbers(config: Dict[str, Any], members, theta: bool = True
               ) -> Tuple[Dict[str, float], int]:
    """Widest backward errors and (with ``theta``) objective gap over the
    fits, and the number of fits whose factor or ``K^-1 y`` is not finite
    (each reads ``inf``)."""
    worst = np.zeros(3)
    bad = 0
    jitter = config["gp"]["jitter"]
    for x, y, t, chol, alpha in members:
        if not (np.isfinite(chol).all() and np.isfinite(alpha).all()):
            bad += 1
            worst[:2] = np.inf
        else:
            worst[:2] = np.maximum(worst[:2], ref.gp_member(
                x, y, t, chol, alpha, jitter))
        if theta:
            worst[2] = max(worst[2], ref.theta_gap(x, y, t, jitter))
    nums = {"gp_chol_err": float(worst[0]), "gp_alpha_err": float(worst[1])}
    if theta:
        nums["gp_theta_gap"] = float(worst[2])
    return nums, bad


def posterior_numbers(posts) -> Dict[str, float]:
    """Widest gaps of the posterior means and variances read, each as a
    share of what rounding can move it by."""
    worst = np.zeros(2)
    for p in posts:
        if not p["members"]:
            continue
        mean, var, m_scale, v_scale = ref.ensemble_posterior(
            p["xq"], p["members"], p["weights"])
        worst = np.maximum(worst, [
            _scaled_gap(p["mean"], mean, m_scale),
            _scaled_gap(p["var"], var, v_scale)])
    return {"gp_mean_err": float(worst[0]), "gp_var_err": float(worst[1])}


def _scaled_gap(got, want, scale) -> float:
    d = np.abs(np.asarray(got, float) - want) / np.maximum(scale, 1e-300)
    return float(np.max(np.where(np.isnan(d), np.inf, d))) if d.size else 0.0


def picks_mismatched(cap) -> int:
    """Optimizing steps whose pick the reference rule does not reproduce
    from the posterior means the step read."""
    bad = 0
    for pk in cap.picks:
        means = [cap.posts[i]["mean"] for i in pk["reads"]]
        want = None
        if len(means) >= 2 and pk["lc"] is not None:
            want = ref.pick(means[0], means[1],
                            means[2] if len(means) > 2 else None,
                            pk["lc"], pk["rc"], pk["sb"])
        if pk["choice"] != want or (want is not None and pk["usage"]
                                    != float(means[0][want])):
            bad += 1
    return bad


def opt_steps_mismatched(cap) -> int:
    """Optimizing steps whose outcome the efficiency rule (paper Sec. 2.4,
    ET) does not reproduce from their pick and the observed usage. Steps
    without a pick (reverts to C_max, unknown workloads) and steps whose
    observed usage is missing are judged only on the configuration they
    apply, which must be C_max."""
    bad = 0
    for st in cap.opt_steps:
        pk = cap.picks[st["picks"][-1]] if st["picks"] else None
        got = st["returned"]
        if pk is None or pk["choice"] is None or not np.isfinite(st["usage"]):
            bad += not (got is None or st["cmax"])
            continue
        saving = (st["usage"] - pk["usage"]) / max(st["usage"], 1e-12)
        want = pk["choice"] if (pk["choice"] != st["current"]
                                and saving >= st["et"]) else None
        bad += got != want
    return bad


def sweep_checks(config: Dict[str, Any], meta: Dict[str, Any], result,
                 cap) -> Tuple[Dict[str, Dict[str, float]], List[str],
                               Dict[str, int]]:
    """Every number of a sweep cell with its limit, the scenarios that
    disagree, and counts reported beside them."""
    rep = replay_inputs(cap, meta)
    sim = ref.simulate(config, meta["rates"], rep["start"],
                       meta["sim_seeds"], rep["decisions"],
                       meta["fail_times"])
    nums, bad = sim_numbers(config, meta, result, sim)
    if cap.fc_reads:
        nums["forecast_rel_err"] = forecast_number(config, cap)
    info = {}
    if "demeter" in meta["controllers"]:
        members = gp_members(cap)
        errs, info["gp_fits_nonfinite"] = gp_numbers(config, members)
        nums.update(errs)
        nums["gp_fits_unchecked"] = float(abs(result.n_model_fits
                                              - len(members)))
        nums.update(posterior_numbers(cap.posts))
        nums["picks_mismatched"] = float(picks_mismatched(cap))
        nums["opt_steps_mismatched"] = float(opt_steps_mismatched(cap))
        nums["profile_pick_gap"] = max(
            (ref.profiling_gap(r, r["picked"]) for r in cap.profiles),
            default=0.0)
        info.update(gp_fits=len(members), posterior_reads=len(cap.posts),
                    picks=len(cap.picks), opt_steps=len(cap.opt_steps),
                    profiling_batches=len(cap.profiles))
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in nums.items()}
    return checks, bad, info


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
