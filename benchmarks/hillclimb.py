"""§Perf hillclimbing: hypothesis -> change -> re-lower -> measure.

Each iteration re-lowers ONE cell (unrolled, single-pod) with a candidate
change (sharding rule override and/or model-config override) and records the
three roofline terms next to the baseline. Results accumulate in
``results/perf_iterations.json``; EXPERIMENTS.md §Perf narrates them.

    PYTHONPATH=src python -m benchmarks.hillclimb --cell mistral_nemo_12b/decode_32k \
        --change kv_seq_shard
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import Dict, Optional, Tuple

#: registry: change id -> (description, cfg overrides, logical rule overrides)
CHANGES: Dict[str, Tuple[str, Dict, Dict]] = {
    "baseline": ("paper-faithful baseline", {}, {}),
    "kv_seq_shard": (
        "shard KV-cache sequence dim on the 16-way model axis when KV heads "
        "cannot (GQA kv<16): per-device cache traffic /16, small LSE-merge "
        "collectives added",
        {}, {"kv_seq_model": "model"}),
    "loss_chunk512": (
        "sequence-chunked cross-entropy (512-position chunks): one chunk of "
        "(tokens, vocab) logits live at a time",
        {"loss_chunk": 512}, {}),
    "loss_chunk512_kvseq": (
        "chunked CE + seq-sharded KV combined",
        {"loss_chunk": 512}, {"kv_seq_model": "model"}),
    "remat_none": (
        "disable remat (trade HBM residency for recompute traffic)",
        {"remat": "none"}, {}),
    "remat_full": (
        "full remat (max recompute, min residency)",
        {"remat": "full"}, {}),
    "cap_factor1": (
        "MoE capacity factor 1.25 -> 1.0 (less dispatch padding traffic)",
        {"_moe_capacity": 1.0}, {}),
    "expert_data_shard": (
        "shard MoE expert-capacity dim on data axis too (2D expert sharding)",
        {}, {"expert_cap": "data"}),
}


def apply_change(arch: str, change: str):
    from repro.configs import get_config
    desc, cfg_over, rules = CHANGES[change]
    cfg = get_config(arch)
    over = dict(cfg_over)
    if "_moe_capacity" in over:
        cap = over.pop("_moe_capacity")
        if cfg.moe is not None:
            over["moe"] = dataclasses.replace(cfg.moe, capacity_factor=cap)
    if over:
        cfg = dataclasses.replace(cfg, **over)
    return cfg, (rules or None), desc


def run(cell: str, change: str, out: str = "results/perf_iterations.json"
        ) -> Dict:
    arch, shape = cell.split("/")
    cfg, rules, desc = apply_change(arch, change)
    from repro.launch.dryrun import lower_cell
    rec = lower_cell(arch, shape, multi_pod=False, cfg_override=cfg,
                     unroll=True, logical_rules=rules)
    rec["change"] = change
    rec["description"] = desc
    results = {}
    if os.path.exists(out):
        with open(out) as f:
            results = json.load(f)
    results[f"{cell}@{change}"] = rec
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    return rec


def summarize(out: str = "results/perf_iterations.json") -> None:
    from .roofline import DRYRUN_DEVICE_KIND, chip_peaks
    peaks = chip_peaks(DRYRUN_DEVICE_KIND)
    with open(out) as f:
        results = json.load(f)
    print(f"{'cell@change':58s} {'compute_s':>9s} {'memory_s':>9s} "
          f"{'coll_s':>9s} {'step_s':>9s}")
    for key in sorted(results):
        r = results[key]
        if r.get("status") != "ok":
            print(f"{key:58s} {r.get('status')}: "
                  f"{str(r.get('error'))[:60]}")
            continue
        c = r["flops"] / peaks.flops
        m = r["bytes_accessed"] / peaks.hbm_bw
        k = r["collective_total"] / peaks.ici_bw
        print(f"{key:58s} {c:9.4f} {m:9.4f} {k:9.4f} {max(c, m, k):9.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", help="arch/shape")
    ap.add_argument("--change", choices=list(CHANGES), default="baseline")
    ap.add_argument("--summary", action="store_true")
    args = ap.parse_args()
    if args.summary:
        summarize()
        return
    rec = run(args.cell, args.change)
    status = rec.get("status")
    if status == "ok":
        print(f"{args.cell}@{args.change}: flops={rec['flops']:.3e} "
              f"bytes={rec['bytes_accessed']:.3e} "
              f"coll={rec['collective_total']:.3e} "
              f"compile={rec['compile_s']}s")
    else:
        print(f"{args.cell}@{args.change}: {status} "
              f"{str(rec.get('error'))[:200]}")


if __name__ == "__main__":
    main()
