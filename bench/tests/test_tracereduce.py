"""The trace reducer: busy union, time per program and idle attribution,
checked against a brute-force timeline, on synthetic events and on a small
trace recorded on a TPU v5e (``data/tpu_v5e_small.xplane.pb``, when
present)."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import tracereduce as tr

DATA = Path(__file__).resolve().parent / "data"


def brute_busy(intervals, lo, hi):
    """Covered length by a difference array over the compressed timeline."""
    iv = [(max(s, lo), min(e, hi)) for s, e in intervals]
    iv = [(s, e) for s, e in iv if e > s]
    pts = np.unique([lo, hi] + [p for se in iv for p in se])
    depth = np.zeros(len(pts), int)
    for s, e in iv:
        depth[np.searchsorted(pts, s)] += 1
        depth[np.searchsorted(pts, e)] -= 1
    covered = np.cumsum(depth)[:-1] > 0
    return float(np.sum(np.diff(pts)[covered]))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_union_matches_a_timeline(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 10_000, 300)
    iv = [(int(s), int(s + d)) for s, d in
          zip(starts, rng.integers(0, 80, 300))]
    lo, hi = 1_000, 9_000
    assert tr.union_length(iv, lo, hi) == brute_busy(iv, lo, hi)
    idle = tr.gaps(iv, lo, hi)
    assert sum(e - s for s, e in idle) == (hi - lo) - brute_busy(iv, lo, hi)
    assert all(lo <= s < e <= hi for s, e in idle)


def test_per_program_strips_ids_and_clips_to_the_window():
    ev = [("jit_fused_interval_scan(3)", 0, 100),
          ("jit_fused_interval_scan(3)", 150, 250),
          ("jit__fit_packed(7)", 240, 400)]
    got = tr.per_program(ev, 50, 300)
    assert got == pytest.approx({"fused_interval_scan": 150e-9,
                                 "_fit_packed": 60e-9})


def test_gaps_go_to_the_innermost_host_annotation():
    host = [("bench.window", 0, 1000), ("sweep.run", 0, 1000),
            ("sweep.policy_block", 100, 400), ("gp_bank.fit", 150, 200)]
    idle = [(160, 180), (300, 340), (600, 700)]
    got = tr.attribute_gaps(idle, host)
    assert got == pytest.approx({"gp_bank.fit": 20e-9,
                                 "sweep.policy_block": 40e-9,
                                 "sweep.run": 100e-9})


def test_reduce_on_synthetic_planes():
    loaded = {"host": [(tr.WINDOW, 100, 1100), ("sweep.run", 100, 1100)],
              "devices": [{tr.MODULES: [("jit_a(1)", 0, 300),
                                        ("jit_b(2)", 500, 700)],
                           tr.OPS: [("x", 0, 150), ("y", 160, 300),
                                    ("z", 500, 700)]}]}
    red = tr.reduce(loaded)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((50 + 140 + 200) * 1e-9)
    assert red["programs"] == pytest.approx({"a": 200e-9, "b": 200e-9})
    assert sum(red["idle_by_host"].values()) == pytest.approx(610e-9)
    bd = tr.breakdown(red)
    assert [k for k, _ in bd["device_ops"]] in (["a", "b"], ["b", "a"])


@pytest.mark.skipif(not (DATA / "tpu_v5e_small.xplane.pb").exists(),
                    reason="no recorded TPU trace in bench/tests/data")
def test_recorded_tpu_trace():
    loaded = tr.load(DATA)
    red = tr.reduce(loaded)
    dev = loaded["devices"][0]
    win = [ev for ev in loaded["host"] if ev[0] == tr.WINDOW][0]
    lo, hi = win[1], win[2]
    ops = [(s, e) for _, s, e in dev[tr.OPS]]
    assert ops, "the device plane has operations"
    assert red["busy_s"] * 1e9 == pytest.approx(brute_busy(ops, lo, hi))
    assert 0 < red["busy_s"] <= red["window_s"]
    mods = dev[tr.MODULES]
    for name, secs in red["programs"].items():
        want = sum(min(e, hi) - max(s, lo) for n, s, e in mods
                   if tr.program_name(n) == name and min(e, hi) > max(s, lo))
        assert secs == pytest.approx(want * 1e-9)
    assert any("matmul_chain" in k for k in red["programs"])
