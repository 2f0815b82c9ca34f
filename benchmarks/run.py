"""Benchmark harness — one module per paper table/figure.

``python -m benchmarks.run [--full]`` prints ``name,us_per_call,derived`` CSV
lines per benchmark (quick mode by default; --full uses paper-scale settings
where the container allows).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()
    quick = not args.full

    from benchmarks import (autotune, decode_throughput, figure1_spectrum,
                            figure3_pretrain, roofline, serving_throughput,
                            table1_complexity, table2_downstream,
                            table3_efficiency, train_step)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    benches = {
        "table1_complexity": table1_complexity.run,
        "figure1_spectrum": figure1_spectrum.run,
        "figure3_pretrain": figure3_pretrain.run,
        "table2_downstream": table2_downstream.run,
        "table3_efficiency": table3_efficiency.run,
        "roofline": roofline.run,
        "decode_throughput": decode_throughput.run,
        # fused Pallas backward vs reference-recompute training step;
        # records BENCH_train_step.json
        "train_step": train_step.run,
        # both serving traces (mixed continuous-vs-static + long-prompt
        # chunked-vs-monolithic admission); records BENCH_serving.json
        "serving_throughput": serving_throughput.run,
    }
    # single-trace serving aliases, --only selectable (CSV only — a partial
    # run never clobbers the committed two-trace BENCH_serving.json)
    aliases = {
        "serving_mixed":
            lambda quick: serving_throughput.run(quick, trace="mixed"),
        "serving_long_prompt":
            lambda quick: serving_throughput.run(quick, trace="long_prompt"),
        # offline autotuner (repro/tune): full mode regenerates the
        # committed TUNING.json; quick mode sweeps toy shapes, so its
        # table goes to a scratch path rather than clobbering it
        "autotune": lambda quick: autotune.run(
            quick, out=("/tmp/tuning_smoke.json" if quick else None)),
    }
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in {**benches, **aliases}.items()
                   if k in keep}

    failures = 0
    for name, fn in benches.items():
        print(f"# === {name} ===")
        t0 = time.time()
        try:
            fn(quick=quick)
            print(f"# {name} done in {time.time() - t0:.1f}s")
        except Exception:
            failures += 1
            print(f"# {name} FAILED")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
