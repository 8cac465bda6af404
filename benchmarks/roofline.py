"""§Roofline: derive the three-term roofline per (arch x shape) cell.

Sources: the unrolled single-pod dry-run (results/roofline_raw.json) for
exact per-device HLO FLOPs / bytes / collective bytes. Hardware peaks come
from :data:`CHIP_PEAKS`, keyed by ``jax.Device.device_kind`` with their
published source; a kind missing from the table is an error, never a
default. The dry-run models a TPU v5e pod (:data:`DRYRUN_DEVICE_KIND`).

cost_analysis of the SPMD-partitioned module reports per-device numbers
(validated against 6·N·D in tests), so terms are directly:

    compute_s    = flops / peak_flops
    memory_s     = bytes_accessed / hbm_bw
    collective_s = collective_bytes / ici_bw

A second section reads the sweep-engine legs from the schema-versioned
bench trajectory (``BENCH_sweep.json``, written by
``benchmarks/sweep_scaling.py --mode fused``) and derives the *dispatch
roofline* for the sweep hot path — writing the derived per-tick numbers
back into the same file under a ``roofline_dispatch`` section: the
batched engine pays one host->XLA dispatch per simulator tick, the fused
engine pays one per decision interval, so

    t_batched_tick = t_step + t_dispatch
    t_fused_tick   = t_step + t_dispatch / K        (K ticks per interval)

and the measured per-tick walls bound t_dispatch from above. The fused
speedup ceiling is (t_step + t_dispatch) / t_step — near 1x on CPU where
dispatch costs microseconds, and the 10x+ regime on accelerator meshes
where the host round-trip dominates a small per-tick step.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, Optional

from repro.configs import get_config
from repro.launch.specs import SHAPES, SHAPE_KIND
from repro.models import param_count
from repro.models.config import ModelConfig

CHIPS = 256


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    flops: float        # dense bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # interchip bytes/s per link
    source: str


#: Per-chip peaks keyed by ``jax.Device.device_kind``.
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip interconnect "
               "(200 GB/s over 4 links = 50 GB/s per link)"),
}

#: The chip whose pod the dry-run's production mesh models.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; raises for a kind with no published row."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                         ) from None


def active_param_count(cfg: ModelConfig) -> int:
    """Per-token activated parameters (MoE: top-k + shared experts only)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    e = cfg.moe
    gates = 3 if cfg.mlp_kind in ("swiglu", "geglu") else 2
    per_expert = gates * cfg.d_model * e.d_expert
    n_moe_layers = cfg.n_layers - e.first_dense_layers
    inactive = (e.n_routed - e.top_k) * per_expert * n_moe_layers
    return total - inactive


def model_flops_per_device(cfg: ModelConfig, shape: str,
                           chips: int = CHIPS) -> float:
    """MODEL_FLOPS: 6·N_active·D for training, 2·N_active·D for inference."""
    seq, batch = SHAPES[shape]
    kind = SHAPE_KIND[shape]
    n = active_param_count(cfg)
    if kind == "train":
        tokens, factor = batch * seq, 6.0
    elif kind == "prefill":
        tokens, factor = batch * seq, 2.0
    else:  # decode: one token per sequence
        tokens, factor = batch * 1, 2.0
    return factor * n * tokens / chips


@dataclass
class RooflineCell:
    arch: str
    shape: str
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops: float
    peak_flops: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak sustained if the step runs at the dominant
        bound: MODEL_FLOPS / (step_s · PEAK)."""
        return self.model_flops / (self.step_s * self.peak_flops) \
            if self.step_s else 0.0


def load_cells(path: str = "results/roofline_raw.json",
               mesh: str = "single",
               device_kind: str = DRYRUN_DEVICE_KIND
               ) -> Dict[str, RooflineCell]:
    peaks = chip_peaks(device_kind)
    with open(path) as f:
        raw = json.load(f)
    cells = {}
    for key, rec in raw.items():
        if rec.get("status") != "ok" or rec.get("mesh") != mesh:
            continue
        cfg = get_config(rec["arch"])
        cell = RooflineCell(
            arch=rec["arch"], shape=rec["shape"],
            compute_s=rec["flops"] / peaks.flops,
            memory_s=rec["bytes_accessed"] / peaks.hbm_bw,
            collective_s=rec["collective_total"] / peaks.ici_bw,
            model_flops=model_flops_per_device(cfg, rec["shape"]),
            hlo_flops=rec["flops"],
            peak_flops=peaks.flops,
        )
        cells[f"{rec['arch']}/{rec['shape']}"] = cell
    return cells


def table(cells: Dict[str, RooflineCell]) -> str:
    hdr = (f"{'cell':42s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'coll_s':>10s} {'bound':>10s} {'MF/HF':>6s} {'roofl%':>7s}")
    rows = [hdr]
    for key in sorted(cells):
        c = cells[key]
        rows.append(f"{key:42s} {c.compute_s:10.4f} {c.memory_s:10.4f} "
                    f"{c.collective_s:10.4f} {c.dominant:>10s} "
                    f"{c.useful_ratio:6.2f} {100*c.roofline_fraction:6.1f}%")
    return "\n".join(rows)


def sweep_dispatch_table(path: str = "BENCH_sweep.json") -> str:
    """Fused-vs-batched dispatch roofline from measured sweep legs.

    Reads the ``mode="fused"`` legs of the ``sweep_scaling`` bench in the
    schema-versioned trajectory file and merges the derived per-tick /
    dispatch-bound numbers back into the same file under a
    ``roofline_dispatch`` section (identity stays in the leg payload).
    """
    from repro.obs import load_bench, make_leg, merge_bench
    legs = load_bench(path)["benches"] \
        .get("sweep_scaling", {}).get("legs", [])
    legs = [r for r in legs if r.get("mode") == "fused"]
    base = next((r for r in legs
                 if r["engine"] == "batched" and r["devices"] == 1), None)
    if base is None or not any(r["engine"] == "fused" for r in legs):
        return (f"# {path} has no fused-vs-batched legs — run "
                "`python benchmarks/sweep_scaling.py --mode fused` first")
    t_batched = base["sweep_wall_s"] / base["n_steps"]
    rows = ["== sweep dispatch roofline (fused vs batched) ==",
            f"{'engine':>8s} {'devices':>8s} {'tick_us':>9s} "
            f"{'scen-steps/s':>13s} {'vs-batched':>11s} {'t_disp_us':>10s}"]
    derived = []
    for r in legs:
        t_tick = r["sweep_wall_s"] / r["n_steps"]
        ratio = r["scenario_steps_per_s"] / base["scenario_steps_per_s"]
        # Per-tick dispatch bound: what the fused scan amortized away.
        # Negative means scan bookkeeping outweighed dispatch on this run
        # (the CPU regime) — report 0, the roofline is dispatch-free.
        t_disp = max(t_batched - t_tick, 0.0) if r["engine"] == "fused" \
            else float("nan")
        rows.append(f"{r['engine']:>8s} {r['devices']:8d} "
                    f"{1e6 * t_tick:9.1f} "
                    f"{r['scenario_steps_per_s']:13.0f} {ratio:11.2f}x "
                    f"{1e6 * t_disp:10.1f}")
        derived.append(make_leg(
            engine=r["engine"], devices=r["devices"],
            seed=r.get("seed", 0), mode="dispatch",
            scenarios=r.get("scenarios"), tick_s=t_tick,
            vs_batched=ratio,
            dispatch_bound_s=None if r["engine"] != "fused" else t_disp))
    merge_bench(path, "roofline_dispatch", derived,
                params={"source": "sweep_scaling[mode=fused]"})
    return "\n".join(rows)


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bench", default="BENCH_sweep.json",
                    help="bench trajectory file holding the fused-vs-"
                         "batched sweep legs (roofline_dispatch is merged "
                         "back into it)")
    args = ap.parse_args()
    if not os.path.exists("results/roofline_raw.json"):
        print("roofline_raw.json missing — run "
              "`python -m repro.launch.dryrun --mesh single --unroll "
              "--out results/roofline_raw.json` first")
    else:
        print(table(load_cells()))
    if os.path.exists(args.bench):
        print()
        print(sweep_dispatch_table(args.bench))


if __name__ == "__main__":
    main()
