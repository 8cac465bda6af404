"""Shared fixtures.

``run_under_devices`` is the multi-device harness: XLA reads
``--xla_force_host_platform_device_count`` exactly once, when the backend
initializes, so a test cannot change the device count of its own process —
each requested count gets a fresh interpreter with the flag injected into
``XLA_FLAGS``. The differential sweep suite (``tests/test_sweep_sharded.py``)
and the golden regression drive ``tests/helpers/sharded_diff.py`` through it
under 1/2/4 virtual devices.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
SRC_DIR = REPO_ROOT / "src"
HELPERS_DIR = Path(__file__).resolve().parent / "helpers"


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _factored_gp(rng, n, dim, shift=0.0):
    """A GP at drawn hyper-parameters, factored in float64 and stored in
    float32 as the GP bank stores its members (no fit, so no compile)."""
    from repro.core import GP
    x = rng.uniform(0, 1, (n, dim))
    y = np.sin(3 * x[:, 0]) + x[:, 1] + shift + rng.normal(0, 0.1, n)
    theta = np.concatenate([np.log(rng.uniform(0.3, 1.0, dim)),
                            [rng.uniform(-0.5, 0.5)],
                            [np.log(rng.uniform(1e-3, 1e-1))]])
    ls, signal, noise = (np.exp(theta[:dim]), np.exp(theta[dim]),
                         np.exp(theta[dim + 1]))
    r = np.sqrt(np.sum(((x[:, None] - x[None]) / ls) ** 2, -1))
    k = signal * (1 + np.sqrt(5) * r + 5 * r ** 2 / 3) \
        * np.exp(-np.sqrt(5) * r) + (noise + 1e-6) * np.eye(n)
    chol = np.linalg.cholesky(k)
    ys = (y - y.mean()) / y.std()
    alpha = np.linalg.solve(chol.T, np.linalg.solve(chol, ys))
    return GP(x=x, y_mean=float(y.mean()), y_std=float(y.std()),
              theta=theta, chol=chol.astype(np.float32),
              alpha=alpha.astype(np.float32))


@pytest.fixture
def make_gp():
    """``make_gp(rng, n, dim, shift=0.0)``: a fitted-looking GP of ``n``
    points in ``dim`` dimensions, built without an optimizer."""
    return _factored_gp


def device_env(n_devices: int) -> dict:
    """An environment with ``n_devices`` virtual XLA host devices.

    Any pre-existing device-count flag is replaced (the suite itself may be
    running under one — the CI matrix leg sets 4); everything else in
    ``XLA_FLAGS`` is preserved. ``PYTHONPATH`` gains ``src/`` so the child
    resolves ``repro`` without an install.
    """
    from repro.distributed.mesh import force_host_device_flags
    env = os.environ.copy()
    env["XLA_FLAGS"] = force_host_device_flags(env.get("XLA_FLAGS", ""),
                                               n_devices)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC_DIR)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH")
                          else []))
    return env


@pytest.fixture
def run_under_devices():
    """Run a helper script in a subprocess with N virtual devices.

    Returns the child's stdout; a non-zero exit fails the calling test with
    both streams attached.
    """
    def run(n_devices: int, script: Path, *args: object,
            timeout: float = 900.0) -> str:
        cmd = [sys.executable, str(script)] + [str(a) for a in args]
        proc = subprocess.run(cmd, env=device_env(n_devices),
                              cwd=str(REPO_ROOT), capture_output=True,
                              text=True, timeout=timeout)
        if proc.returncode != 0:
            pytest.fail(
                f"subprocess failed (devices={n_devices}): {' '.join(cmd)}\n"
                f"--- stdout ---\n{proc.stdout}\n"
                f"--- stderr ---\n{proc.stderr}")
        return proc.stdout
    return run
