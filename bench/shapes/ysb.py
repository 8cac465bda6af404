"""The YSB job's click stream (Demeter, arXiv 2403.02129, Sec. 3.4): a
mean wandering over hours between knots, an Ornstein-Uhlenbeck fluctuation
over minutes and occasional spikes, clipped to the configured range; no
trend. A copy of the program's ``repro.dsp.workloads.ysb_like`` with its
constants read from the configuration's ``trace``."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def rates(tr: Dict[str, Any], n: int, dt_s: float,
          seeds: np.ndarray) -> np.ndarray:
    """``[len(seeds), n]`` events/s, one row per seed."""
    S = len(seeds)
    rngs = [np.random.default_rng(int(s)) for s in seeds]
    t = np.arange(n) * dt_s
    span = n * dt_s
    knot_t = np.linspace(0.0, span, tr["knots"])
    mean = np.stack([np.interp(t, knot_t,
                               g.uniform(tr["knot_lo"], tr["knot_hi"],
                                         tr["knots"])) for g in rngs])
    z = np.stack([g.standard_normal(n) for g in rngs])
    theta, sigma = tr["ou_theta_per_s"], tr["ou_sigma"]
    ou = np.zeros((S, n))
    for i in range(1, n):
        ou[:, i] = ou[:, i - 1] * (1.0 - theta * dt_s) \
            + sigma * np.sqrt(dt_s) * z[:, i]
    spikes = np.zeros((S, n))
    for j, g in enumerate(rngs):
        for _ in range(tr["spikes"]):
            c = int(g.integers(0, n))
            w = int(g.uniform(*tr["spike_halfwidth_s"]) / dt_s)
            amp = g.uniform(*tr["spike_amp"]) * g.choice([-1.0, 1.0])
            lo, hi = max(c - w, 0), min(c + w, n)
            spikes[j, lo:hi] += amp * np.hanning(hi - lo)
    return np.clip(mean + ou + spikes, tr["lo"], tr["hi"])
