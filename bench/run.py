"""Run one benchmark cell and print its result line.

    python -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout of the repository, on a machine with the chips
the cell asks for. With --trace 0 the result holds the cell's end-to-end
metrics; with --trace 1 its per-layer metrics, read from a profiler trace
of the middle of the window. Without a TPU of a kind in bench/peaks.json
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def execute(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """One run of a cell: the device check, the compilation cache, then the
    runner of the cell's traffic kind (bench/cells/<kind>.py)."""
    from bench import harness
    cell = harness.load_cell(workload)
    device = harness.check_device(cell.chips)
    harness.enable_compile_cache()
    return harness.runner(cell).run(cell, seed, seconds, trace, device,
                                    T_START)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.pop("REPRO_TUNING_PATH", None)
    from bench import harness
    try:
        out = execute(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except harness.NoDevice as e:
        print(f"[bench] not run: {e}", file=sys.stderr)
        return 2
    harness.emit(**out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
