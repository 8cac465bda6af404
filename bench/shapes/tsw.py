"""The TSW job's vehicle counts (Demeter, arXiv 2403.02129, Sec. 3.4):
three repetitions of a 6 h "day" (a seasonal sinusoid with a second
harmonic), a weak upward trend and smoothed noise, clipped to the
configured range. A copy of the program's ``repro.dsp.workloads.tsw_like``
with its constants read from the configuration's ``trace``."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _hann_smooth(x: np.ndarray, window_s: float, dt_s: float) -> np.ndarray:
    """Hanning-smooth each row of ``x`` (``[S, n]``)."""
    k = min(max(int(window_s / dt_s), 3), x.shape[1])
    kern = np.hanning(k)
    kern /= kern.sum()
    return np.stack([np.convolve(row, kern, mode="same") for row in x])


def rates(tr: Dict[str, Any], n: int, dt_s: float,
          seeds: np.ndarray) -> np.ndarray:
    """``[len(seeds), n]`` events/s, one row per seed."""
    t = np.arange(n) * dt_s
    day = tr["day_s"]
    phase = 2.0 * np.pi * (t % day) / day
    seasonal = tr["base"] + tr["amp1"] * np.sin(phase - np.pi / 2) \
        + tr["amp2"] * np.sin(2 * phase)
    trend = tr["trend"] * t / (n * dt_s)
    z = np.stack([np.random.default_rng(int(s)).standard_normal(n)
                  for s in seeds])
    noise = _hann_smooth(tr["noise_sd"] * z, tr["noise_smooth_s"], dt_s)
    return np.clip(seasonal + trend + noise, tr["lo"], tr["hi"])
