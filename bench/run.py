"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run needs
the TPU chips the cell asks for and exits non-zero, printing no result,
without them. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiled window. Either way the
last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
``checks`` last: each compared number with its limit); progress and
per-phase numbers go to earlier lines.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    cell = harness.load_cell(args.workload)
    devs = harness.require_tpu(int(cell["workload"]["chips"]))
    harness.import_program()
    harness.log(f"{len(devs)} x {devs[0].device_kind}; compile cache "
                f"{harness.enable_compile_cache()}")
    harness.OUT.mkdir(exist_ok=True)
    result = harness.load_kind(cell).run(cell, args.seed, args.seconds,
                                         bool(args.trace), T_START, devs)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
