"""The ``gp_packed_reads_per_kstep`` reader on a registry filled through
``obs.inc``, in the form of the ``gp_single_reads_per_kstep`` case."""
from __future__ import annotations

import pytest

from bench import harness
from repro import obs


def read(metric, scenario_steps):
    return harness.load_reader(metric)(
        {"spans": [], "window_s": 1.0, "scenario_steps": scenario_steps})


@pytest.fixture
def registry():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def test_gp_packed_reads_per_kstep(registry):
    assert read("gp_packed_reads_per_kstep", scenario_steps=4000) is None
    obs.inc("gp.packed_reads")              # off: not counted
    assert read("gp_packed_reads_per_kstep", scenario_steps=4000) is None
    obs.enable()
    for _ in range(6):
        obs.inc("gp.packed_reads")
    obs.inc("gp.single_reads", 0)
    obs.disable()
    assert read("gp_packed_reads_per_kstep",
                scenario_steps=4000) == pytest.approx(1.5)
    assert read("gp_single_reads_per_kstep", scenario_steps=4000) == 0
    assert read("gp_packed_reads_per_kstep", scenario_steps=0) is None
