"""What every traffic kind shares: words drawn from ``--seed`` and the rate
traces of a configuration.

A configuration's ``trace.shape`` names ``bench/shapes/<shape>.py``, whose
``rates(trace, n, dt_s, seeds)`` draws one row of arrival rates per seed.
Traces are generated at their published length and cut to the
configuration's ``duration_s`` (the first hours of the published run). A
mix's ``kind`` names its driver, ``bench/kinds/<kind>.py``, which builds the
cell's inputs from these.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench import harness


def seed_words(seed: int, n: int) -> np.ndarray:
    """``n`` independent 32-bit words from ``seed`` (any non-negative int)."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def rate_traces(config: Dict[str, Any], seeds: np.ndarray) -> np.ndarray:
    """``[S, n]`` arrival rates (events/s): the published run per seed, cut
    to the configuration's duration."""
    tr = config["trace"]
    dt = config["dt_s"]
    shape = harness.load_named("shapes", tr["shape"])
    full = shape.rates(tr, int(config["published"]["duration_s"] / dt), dt,
                       seeds)
    return full[:, :int(config["duration_s"] / dt)]
