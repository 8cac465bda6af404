"""The cell, configuration, mix and reader loaders, and the traffic
generator, at a tiny size on the CPU."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import harness, traffic

BENCH = harness.load_benchmark()


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(w):
    cell = harness.load_cell(w["name"])
    assert callable(harness.load_kind(cell).run)
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert cell["per_layer"], "every cell reports a per-layer metric"
    for m in cell["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(c):
    cfg = json.loads((harness.ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"]
    assert cfg["reduced"] == c["reduced"]
    for key in c["reduced"]:
        assert cfg[key] != cfg["published"][key]
        assert cfg["cuts"][key]
    assert cfg["published"]["duration_s"] >= cfg["duration_s"]


def test_unknown_cell_is_refused():
    with pytest.raises(harness.CellError):
        harness.load_cell("no-such-cell")


@pytest.mark.parametrize("folder", ["kinds", "shapes", "metrics"])
def test_unknown_file_by_name_is_refused(folder):
    with pytest.raises(harness.CellError):
        harness.load_named(folder, "no-such-name")


def test_kind_found_by_name_is_loaded_once():
    cell = harness.load_cell("tsw-sweep-baselines")
    assert harness.load_kind(cell) is harness.load_named("kinds", "sweep")


@pytest.mark.parametrize("name", ["ysb", "tsw"])
def test_rates_are_seeded_in_range_and_cut(name):
    cfg = json.loads((harness.BENCH / "configs" / f"{name}.json").read_text())
    cfg = dict(cfg, published={"duration_s": 7200.0}, duration_s=3600.0)
    seeds = traffic.seed_words(2 ** 31 + 12345, 3)
    a = traffic.rate_traces(cfg, seeds)
    b = traffic.rate_traces(cfg, seeds)
    assert a.shape == (3, 720)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= cfg["trace"]["lo"] and a.max() <= cfg["trace"]["hi"]
    assert not np.array_equal(a[0], a[1])


def test_sweep_grid_sizes_do_not_depend_on_the_seed():
    from conftest import small_cell
    cell = small_cell("tsw-sweep-baselines", 2, 1800.0)
    grid = harness.load_kind(cell).sweep_grid
    grids = [grid(cell["config"], cell["mix"], s)
             for s in (1, 2 ** 33)]
    for specs, meta in grids:
        assert [s.controller for s in specs] == \
            ["static"] * 2 + ["reactive"] * 2 + ["ds2"] * 2
        assert meta["rates"].shape == (6, 360)
        np.testing.assert_array_equal(meta["fail_times"], [])
    assert grids[0][1]["sim_seeds"] != grids[1][1]["sim_seeds"]
