"""Readings that the limits in ``limits.json`` are set from: the program's
numbers and the control's, seed by seed, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--out F]

For each seed it runs the cell's grid once through the program (as the
window does) and prints one JSON line with

* ``program`` — the numbers :mod:`bench.check` compares (the lower
  readings);
* ``control`` — the same numbers for the control: the reference put in the
  program's place one precision below what the configuration states, read
  against the float64 reference on the same teacher-forced inputs. The
  simulator and the forecaster run in float32 (the configuration states
  float64). The GP paths run in float32 with every matmul at precision
  ``high`` (the configuration states ``highest``), as the program would
  with that precision: the kernel matrix, its Cholesky factor and
  ``K^-1 y``; the multi-restart L-BFGS fit from the program's own restart
  starts; the ensemble posteriors. The profiling acquisition (float32 in
  the configuration) runs in bfloat16 and picks its greedy batch from
  those scores. ``default_precision`` holds the GP numbers at the TPU's
  default matmul precision, for comparison.

With ``--dump F`` the first seed's capture is also pickled to ``F``, for
working on the comparison without the chip. The benchmark's own runs never
run this. It needs the chip for the program's run and the GP controls; the
rest runs on the host.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pickle
import sys
import time
from functools import partial
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import capture, check, harness, reference as ref  # noqa: E402


def gp_control(config: Dict[str, Any], members, precision: str
               ) -> Dict[str, float]:
    """The GP numbers for a float32 fit state computed at ``precision``."""
    import jax
    import jax.numpy as jnp

    prec = getattr(jax.lax.Precision, precision.upper())
    jitter = config["gp"]["jitter"]

    @jax.jit
    def factor(x, y, theta):
        d = x.shape[1]
        ls, signal, noise = (jnp.exp(theta[:d]), jnp.exp(theta[d]),
                             jnp.exp(theta[d + 1]))
        z = x / ls
        sq = jnp.sum(z * z, -1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(z, z.T,
                                                          precision=prec)
        d2 = jnp.maximum(d2, 1e-12)
        s5r = jnp.sqrt(5.0) * jnp.sqrt(d2)
        K = signal * (1 + s5r + 5.0 * d2 / 3) * jnp.exp(-s5r) \
            + (noise + jitter) * jnp.eye(x.shape[0])
        L = jnp.linalg.cholesky(K)
        a = jax.scipy.linalg.cho_solve((L, True), y)
        return L, a

    fits = []
    for x, y, theta, _chol, _alpha in members:
        L, a = factor(jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
                      jnp.asarray(theta, jnp.float32))
        fits.append((x, y, theta, np.asarray(L), np.asarray(a)))
    errs, nonfinite = check.gp_numbers(config, fits, theta=False)
    return {**errs, "gp_fits_nonfinite": nonfinite}


def forecast_control(config: Dict[str, Any], cap) -> float:
    """Widest gap of float32 forecaster reads against float64 ones."""
    fc = config["forecaster"]
    worst = 0.0
    rows: Dict[int, List[int]] = {}
    for row, n, _ in cap.fc_reads:
        rows.setdefault(row, []).append(n)
    for row, ns in rows.items():
        models = [ref.Arima(fc, np.float64), ref.Arima(fc, np.float32)]
        obs = cap.fc_updates[row]
        fed = 0
        for n in sorted(ns):
            while fed < n:
                for mdl in models:
                    mdl.update(obs[fed])
                fed += 1
            want, got = (ref.max_bin(mdl.forecast(fc["horizon"]), fc["bins"])
                         for mdl in models)
            worst = max(worst, abs(got - want) / max(abs(want), 1.0))
    return worst


def _kernel(xa, xb, theta, prec):
    import jax.numpy as jnp
    d = xa.shape[1]
    ls, signal = jnp.exp(theta[:d]), jnp.exp(theta[d])
    za, zb = xa / ls, xb / ls
    d2 = jnp.sum(za * za, -1)[:, None] + jnp.sum(zb * zb, -1)[None, :] \
        - 2.0 * jnp.matmul(za, zb.T, precision=prec)
    s5r = jnp.sqrt(5.0) * jnp.sqrt(jnp.maximum(d2, 1e-12))
    return signal * (1 + s5r + 5.0 * d2 / 3) * jnp.exp(-s5r)


def fit_control(config: Dict[str, Any], cap, precision: str
                ) -> Dict[str, float]:
    """``gp_theta_gap`` of float32 fits at ``precision``: multi-restart
    L-BFGS (optax, the program's stopping rule: gradient norm 1e-5 or
    ``max_iter``) of the masked objective from each dispatch's own restart
    starts, the best finite restart kept."""
    import jax
    import jax.numpy as jnp
    import optax
    import optax.tree_utils as otu

    prec = getattr(jax.lax.Precision, precision.upper())
    jitter = config["gp"]["jitter"]
    (m_ls, v_ls), (m_s, v_s), (m_n, v_n) = ref.PRIORS

    def objective(theta, x, y, mask):
        n, d = x.shape
        K = _kernel(x, x, theta, prec) \
            + (jnp.exp(theta[d + 1]) + jitter) * jnp.eye(n)
        K = jnp.where(mask[:, None] * mask[None, :] > 0, K, 0.0) \
            + jnp.diag(1.0 - mask)
        L = jnp.linalg.cholesky(K)
        a = jax.scipy.linalg.cho_solve((L, True), y)
        nll = 0.5 * jnp.matmul(y, a, precision=prec) \
            + jnp.sum(jnp.log(jnp.diagonal(L)) * mask) \
            + 0.5 * jnp.sum(mask) * jnp.log(2 * jnp.pi)
        prior = (jnp.sum((theta[:d] - m_ls) ** 2) / (2 * v_ls)
                 + (theta[d] - m_s) ** 2 / (2 * v_s)
                 + (theta[d + 1] - m_n) ** 2 / (2 * v_n))
        return nll + prior

    @partial(jax.jit, static_argnames="max_iter")
    def fit(x, y, mask, t0s, max_iter):
        def one(xi, yi, mi, starts):
            fun = lambda th: objective(th, xi, yi, mi)  # noqa: E731
            opt = optax.lbfgs()
            vg = optax.value_and_grad_from_state(fun)

            def cond(carry):
                _, st = carry
                k = otu.tree_get(st, "count")
                return (k == 0) | ((k < max_iter) & (
                    otu.tree_norm(otu.tree_get(st, "grad")) > 1e-5))

            def body(carry):
                t, st = carry
                v, g = vg(t, state=st)
                u, st = opt.update(g, st, t, value=v, grad=g, value_fn=fun)
                return optax.apply_updates(t, u), st

            def run(t0):
                t, _ = jax.lax.while_loop(cond, body, (t0, opt.init(t0)))
                return t, fun(t)

            ts, vs = jax.vmap(run)(starts)
            vs = jnp.where(jnp.isfinite(vs), vs, jnp.inf)
            return ts[jnp.argmin(vs)]
        return jax.vmap(one)(x, y, mask, t0s)

    worst = 0.0
    for x, y, mask, *_rest, t0s, max_iter in cap.gp_fits:
        x, y, mask = (np.asarray(a) for a in (x, y, mask))
        theta = np.asarray(fit(jnp.asarray(x, jnp.float32),
                               jnp.asarray(y, jnp.float32),
                               jnp.asarray(mask, jnp.float32),
                               jnp.asarray(np.asarray(t0s), jnp.float32),
                               max_iter=int(max_iter)))
        for i in range(len(mask)):
            n = int(mask[i].sum())
            if n >= 2:
                worst = max(worst, ref.theta_gap(x[i, :n], y[i, :n],
                                                 theta[i], jitter))
    return {"gp_theta_gap": worst}


def posterior_control(cap, precision: str) -> Dict[str, float]:
    """``gp_mean_err``/``gp_var_err`` of ensemble posteriors computed in
    float32 at ``precision`` from the same members, weights and factors."""
    import jax
    import jax.numpy as jnp

    prec = getattr(jax.lax.Precision, precision.upper())

    @jax.jit
    def member(xq, x, theta, chol, alpha):
        ks = _kernel(xq, x, theta, prec)
        v = jax.scipy.linalg.solve_triangular(chol, ks.T, lower=True)
        signal = jnp.exp(theta[x.shape[1]])
        return jnp.matmul(ks, alpha, precision=prec), \
            jnp.maximum(signal - jnp.sum(v * v, axis=0), 1e-10)

    worst = np.zeros(2)
    f32 = np.float32
    for p in cap.posts:
        if not p["members"]:
            continue
        xq = jnp.asarray(np.atleast_2d(p["xq"]), jnp.float32)
        mean = np.zeros(xq.shape[0], f32)
        var = np.zeros(xq.shape[0], f32)
        for (x, theta, chol, alpha, y_mean, y_std), w in zip(
                p["members"], p["weights"]):
            m, v = (np.asarray(a) for a in member(
                xq, *(jnp.asarray(a, jnp.float32)
                      for a in (x, theta, chol, alpha))))
            mean += f32(w) * (m * f32(y_std) + f32(y_mean))
            var += f32(w * w) * v * f32(y_std) ** 2
        want_m, want_v, m_scale, v_scale = ref.ensemble_posterior(
            p["xq"], p["members"], p["weights"])
        worst = np.maximum(worst, [
            check._scaled_gap(mean, want_m, m_scale),
            check._scaled_gap(np.maximum(var, 1e-12), want_v, v_scale)])
    return {"gp_mean_err": float(worst[0]), "gp_var_err": float(worst[1])}


def profiling_control(cap, dtype: str = "bfloat16") -> Dict[str, float]:
    """``profile_pick_gap`` of greedy batches picked from acquisition
    scores computed in ``dtype`` (the normal CDF and density evaluated in
    float32 and rounded to ``dtype``, where JAX has no ``dtype`` kernel)."""
    import jax.numpy as jnp
    from jax.scipy import stats as jstats

    dt = getattr(jnp, dtype)

    class norm:
        @staticmethod
        def cdf(z):
            return jstats.norm.cdf(z.astype(jnp.float32)).astype(dt)

        @staticmethod
        def pdf(z):
            return jstats.norm.pdf(z.astype(jnp.float32)).astype(dt)

    def ramp(c, mu, sd):
        sd = jnp.maximum(sd, dt(1e-12))
        neg_inf = jnp.isneginf(c)
        c0 = jnp.where(neg_inf, dt(0), c)
        z = (c0 - mu) / sd
        return jnp.where(neg_inf, dt(0),
                         (c0 - mu) * norm.cdf(z) + sd * norm.pdf(z))

    def scores(rec, front):
        mu = jnp.asarray(rec["mu"], dt)
        sd = jnp.sqrt(jnp.maximum(jnp.asarray(rec["var"], dt), dt(1e-18)))
        f = ref.pareto_front(front)
        r0, r1 = rec["ref"]
        f = f[(f[:, 0] < r0) & (f[:, 1] < r1)]
        edges = jnp.asarray(np.concatenate([[-np.inf], f[:, 0], [r0]]), dt)
        heights = jnp.asarray(np.concatenate([[r1], f[:, 1]]), dt)
        right = ramp(jnp.minimum(edges[1:], dt(r0))[None, :], mu[:, :1],
                     sd[:, :1])
        left = ramp(edges[:-1][None, :], mu[:, :1], sd[:, :1])
        out = jnp.sum(jnp.maximum(right - left, dt(0))
                      * ramp(heights[None, :], mu[:, 1:], sd[:, 1:]), axis=1)
        if rec.get("rmu") is not None and rec["rc"] is not None:
            rsd = jnp.sqrt(jnp.maximum(jnp.asarray(rec["rvar"], dt),
                                       dt(1e-18)))
            out = out * norm.cdf((dt(rec["rc"]) - jnp.asarray(rec["rmu"], dt))
                                 / rsd)
        if rec["bias"] is not None:
            out = out * jnp.asarray(rec["bias"], dt)
        return np.asarray(out.astype(jnp.float32), np.float64)

    worst = 0.0
    for rec in cap.profiles:
        dead = np.zeros(len(rec["mu"]), bool)
        dead[rec["exclude"]] = True
        front = rec["front"]
        picked = []
        for _ in range(rec["q"]):
            s = scores(rec, front)
            s[dead] = -np.inf
            j = int(np.argmax(s))
            if not np.isfinite(s[j]) or s[j] <= 0:
                break
            picked.append(j)
            dead[j] = True
            front = np.vstack([front, np.asarray(rec["mu"])[j]])
            if dead.all():
                break
        worst = max(worst, ref.profiling_gap(rec, picked))
    return {"profile_pick_gap": worst}


def readings(cell: Dict[str, Any], seed: int,
             dump: Optional[str] = None) -> Dict[str, Any]:
    build_engine = harness.load_kind(cell).build_engine
    config = cell["config"]
    engine, meta = build_engine(config, cell["mix"], seed)
    cap = capture.Capture()
    capture.install(cap)
    t0 = time.perf_counter()
    res = engine.run()
    wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    program, bad, info = check.sweep_checks(config, meta, res, cap)
    check_s = time.perf_counter() - t0
    rep = check.replay_inputs(cap, meta)
    args = (config, meta["rates"], rep["start"], meta["sim_seeds"],
            rep["decisions"], meta["fail_times"])
    sim64 = ref.simulate(*args)
    sim32 = ref.simulate(*args, dtype=np.float32)
    worst = max(check.rel_err(sim32.arrays[k], sim64.arrays[k])
                for k in check.ARRAYS)
    control = {"sim_rel_err": worst}
    if cap.fc_reads:
        control["forecast_rel_err"] = forecast_control(config, cap)
    members = check.gp_members(cap)
    if members:
        control.update(gp_control(config, members, "high"))
        control.update(fit_control(config, cap, "high"))
        control.update(posterior_control(cap, "high"))
        control.update(profiling_control(cap))
        control["default_precision"] = {
            **gp_control(config, members, "default"),
            **posterior_control(cap, "default")}
    if dump:
        with gzip.open(dump, "wb") as f:
            pickle.dump({"config": config, "meta": meta, "cap": _host(cap)},
                        f)
    return {"seed": seed, "sweep_wall_s": wall, "check_s": check_s,
            "gp_fits": res.n_model_fits, "decisions": len(cap.decisions),
            "disagree": bad,
            "program": {**{k: v["value"] for k, v in program.items()},
                        **info},
            "control": control}


def _host(cap) -> Dict[str, Any]:
    """The capture with device arrays read back, as plain data."""
    out = dict(vars(cap))
    out["gp_fits"] = [tuple(np.asarray(a) for a in f[:8]) + (f[8],)
                      for f in cap.gp_fits]
    out["fc_updates"] = dict(cap.fc_updates)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated non-negative integers")
    ap.add_argument("--out", default=None, help="also append lines here")
    ap.add_argument("--dump", default=None,
                    help="pickle the first seed's capture here (gzip)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.import_program()
    harness.enable_compile_cache()
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        line = json.dumps(readings(cell, seed, args.dump if k == 0 else None))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
