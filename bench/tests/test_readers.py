"""The per-layer readers of the program's spans and counters, on synthetic
span records and a registry filled through ``obs.inc``."""
from __future__ import annotations

import pytest

from bench import harness
from repro import obs
from repro.obs.trace import SpanRecord

MS = 1_000_000                       # ns


def rec(name, t_ms, dur_ms, depth):
    return SpanRecord(name, int(t_ms * MS), int(dur_ms * MS), depth)


def read(metric, spans=(), window_s=1.0, scenario_steps=0):
    return harness.load_reader(metric)(
        {"spans": list(spans), "window_s": window_s,
         "scenario_steps": scenario_steps})


# one sweep of 1 s: two interval steps, a model refresh and a policy block
# whose controller builds two ensembles, one of them inside another
SWEEP = [
    rec("sweep.run", 0, 1000, 0),
    rec("engine.fused.prepare", 10, 100, 1),
    rec("engine.fused.interval", 110, 20, 1),
    rec("engine.fused.readback", 130, 30, 1),
    rec("sweep.model_refresh", 200, 150, 1),
    rec("gp_bank.fit", 210, 120, 2),
    rec("sweep.policy_block", 400, 300, 1),
    rec("demeter.ensemble", 410, 100, 2),
    rec("demeter.ensemble", 420, 50, 3),     # nested: counted once
    rec("demeter.ensemble", 600, 40, 2),
    rec("engine.fused.prepare", 800, 50, 1),
    rec("engine.fused.interval", 850, 10, 1),
    rec("engine.fused.readback", 860, 20, 1),
]


@pytest.mark.parametrize("metric, share", [
    ("interval_prep_share", 15.0),
    ("interval_readback_share", 5.0),
    ("model_refresh_share", 15.0),
    ("rgpe_build_share", 14.0),
    # 1000 ms less the direct children: 150 + 150 + 300 + 80
    ("sweep_loop_self_share", 32.0),
])
def test_span_readers(metric, share):
    assert read(metric, SWEEP) == pytest.approx(share)
    assert read(metric, SWEEP, window_s=2.0) == pytest.approx(share / 2)


@pytest.mark.parametrize("metric", [
    "interval_prep_share", "interval_readback_share", "model_refresh_share",
    "rgpe_build_share", "sweep_loop_self_share"])
def test_span_readers_without_their_span(metric):
    others = [s for s in SWEEP if s.name not in (
        {"sweep.run"} if metric == "sweep_loop_self_share" else
        {"engine.fused.prepare", "engine.fused.readback",
         "sweep.model_refresh", "demeter.ensemble"})]
    assert read(metric, others) is None
    assert read(metric) is None


def test_loop_self_share_counts_only_direct_children_inside_the_run():
    spans = [rec("sweep.run", 0, 100, 1),
             rec("engine.fused.interval", 10, 20, 2),
             rec("gp_bank.fit", 40, 10, 3),            # a grandchild
             rec("engine.fused.interval", 150, 20, 2),  # after the run
             rec("sweep.run", 200, 100, 1),
             rec("sweep.policy_block", 210, 50, 2)]
    assert read("sweep_loop_self_share", spans) == pytest.approx(13.0)


def test_rgpe_build_share_counts_overlap_once():
    spans = [rec("demeter.ensemble", 0, 100, 0),
             rec("demeter.ensemble", 10, 20, 1),
             rec("demeter.ensemble", 200, 50, 0),
             rec("demeter.ensemble", 210, 40, 1)]
    assert read("rgpe_build_share", spans) == pytest.approx(15.0)


@pytest.fixture
def registry():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def test_gp_single_reads_per_kstep(registry):
    assert read("gp_single_reads_per_kstep", scenario_steps=4000) is None
    obs.inc("gp.single_reads")              # off: not counted
    assert read("gp_single_reads_per_kstep", scenario_steps=4000) is None
    obs.enable()
    for _ in range(6):
        obs.inc("gp.single_reads")
    obs.inc("sweep.ticks", 99)
    obs.disable()
    assert read("gp_single_reads_per_kstep",
                scenario_steps=4000) == pytest.approx(1.5)
    assert read("gp_single_reads_per_kstep", scenario_steps=0) is None
