"""Readings that the correctness limits and the cells' rates are set from.
The benchmark's own runs never run this.

    python -m bench.calibrate --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--seconds 20] [--sweep 1.5,2,3]

One process; the runner of the cell's kind (bench/cells/<kind>.py, its
`calibrate`) reads, for each seed, the numbers that the cell compares for
the program and, on the control seeds, for the control and the faults the
check has to catch. Each reading is printed as one JSON line, with the
numbers held to the cell's committed limits (bench/limits/<cell>.json)
under `checks` and the verdict under `correct`.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    cell = harness.load_cell(args.workload)
    device = harness.check_device(cell.chips)
    harness.enable_compile_cache()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    harness.runner(cell).calibrate(
        cell, ints(args.seeds), set(ints(args.control_seeds)), args.seconds,
        [float(x) for x in args.sweep.split(",") if x], device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
