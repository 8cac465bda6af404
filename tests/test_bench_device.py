"""Device handling in the benchmark scripts.

``roofline.py`` reads a chip's peaks from a table keyed by ``device_kind``
and refuses a kind it does not know. ``sweep_scaling.py`` decides from
``JAX_PLATFORMS`` alone, before touching a device, whether its legs run as
forced-device CPU children or in its own process (a chip belongs to one
process, so an accelerator host runs every leg in-process).
"""
import argparse
import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmarks"


def _load(name):
    spec = importlib.util.spec_from_file_location(name, BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses resolve their module here
    spec.loader.exec_module(mod)
    return mod


def test_roofline_peaks_are_keyed_by_device_kind():
    roofline = _load("roofline")
    v5e = roofline.chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.chip_peaks("cpu")


@pytest.mark.parametrize("platforms, forced", [
    ("cpu", True), (" CPU ", True), ("tpu", False), ("", False)])
def test_sweep_scaling_mode_follows_jax_platforms(monkeypatch, platforms,
                                                  forced):
    scaling = _load("sweep_scaling")
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert scaling.forced_host_devices() is forced


def test_sweep_scaling_runs_legs_in_process_off_the_cpu(monkeypatch):
    scaling = _load("sweep_scaling")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    def no_child(n):
        raise AssertionError("an accelerator host must not spawn a child")

    monkeypatch.setattr(scaling, "device_env", no_child)
    args = argparse.Namespace(duration_h=60.0 / 3600.0, dt=5.0)
    leg = scaling.run_leg(1, 2, args, "batched")
    assert leg["devices"] == 1 and leg["scenarios"] == 2
    assert leg["n_steps"] == 12
    # A width past the visible devices fails the leg; it never spawns.
    assert scaling.run_leg(64, 2, args, "fused") is None
