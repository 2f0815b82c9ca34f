"""Serving launcher: load (or init) weights for an arch and serve synthetic
mixed-length traffic through the continuous-batching scheduler (default) or
the static bucketed baseline.

    python -m repro.launch.serve --arch qwen3-8b --smoke --requests 8
    python -m repro.launch.serve --arch qwen3-8b --smoke --scheduler static
    python -m repro.launch.serve --arch qwen3-8b --smoke --requests 12 \
        --max-batch 2 --priority-classes 3 --deadline-ticks 8 --max-queue 6
"""
import argparse
import dataclasses
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attention", default=None)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from this checkpoint dir")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--backend", default=None,
                    choices=["auto", "reference", "fused"],
                    help="attention compute backend (default: config's "
                         "'auto' -> fused Pallas kernels)")
    ap.add_argument("--decode-chunk", type=int, default=32,
                    help="tokens per device-resident decode scan chunk")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked admission prefill: stream each prompt "
                         "into its slot in fixed chunks of this many tokens "
                         "(multiple of the attention block size), "
                         "interleaved with decode and batched across "
                         "co-prefilling requests; 0 = monolithic B=1 "
                         "admission prefill")
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "static"],
                    help="continuous: slot-based admission/eviction between "
                         "decode chunks; static: equal-length bucketed "
                         "batches (baseline)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="assign synthetic requests round-robin to this many "
                         "priority classes (0 = most urgent; urgent arrivals "
                         "preempt running lower-priority slots); 1 = all "
                         "priority 0, plain FCFS")
    ap.add_argument("--deadline-ticks", type=int, default=0,
                    help="give every priority-0 request an absolute deadline "
                         "this many scheduler ticks out (0 = no deadlines); "
                         "provably-infeasible deadlines are shed at "
                         "admission")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the admission queue to this many waiting "
                         "requests; overflow sheds the least-valued entry "
                         "(0 = unbounded)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(host spans + one lane per request) to this path; "
                         "enables telemetry")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics dump (scheduler counters, "
                         "per-priority TTFT/TPOT/queue-wait histograms, "
                         "plan cost attribution) as JSONL to this path; "
                         "enables telemetry")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.checkpoint import Checkpointer
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M
    from repro.serving import ServingEngine

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg, dtype="float32")
    if args.attention and cfg.family != "ssm":
        cfg = cfg.with_attention_kind(args.attention)

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        ck = Checkpointer(args.ckpt_dir)
        restored, meta = ck.restore_latest({"params": params})
        if restored:
            params = restored["params"]
            print(f"[serve] restored step {meta['step']} from {args.ckpt_dir}")

    from repro.telemetry import Telemetry
    telemetry = (Telemetry() if args.trace_out or args.metrics_out
                 else None)
    eng = ServingEngine(params, cfg, max_seq=args.max_seq,
                        cache_dtype=jnp.float32 if args.smoke else jnp.bfloat16,
                        temperature=args.temperature,
                        decode_chunk=args.decode_chunk,
                        attention_backend=args.backend,
                        prefill_chunk=args.prefill_chunk,
                        telemetry=telemetry)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(4, cfg.vocab_size,
                                 int(rng.choice([8, 16, 16, 32]))))
               for _ in range(args.requests)]
    mode = args.scheduler
    if mode == "continuous" and not eng.supports_continuous_batching:
        print(f"[serve] {cfg.family!r} cache has no per-row positions; "
              "falling back to the static bucketed scheduler")
        mode = "static"
    prios = ([i % args.priority_classes for i in range(len(prompts))]
             if args.priority_classes > 1 else None)
    deadlines = None
    if args.deadline_ticks:
        deadlines = [args.deadline_ticks if (prios is None or p == 0) else None
                     for p in (prios or [0] * len(prompts))]
    t0 = time.perf_counter()
    if mode == "continuous":
        outs, sched = eng.serve(prompts, args.max_new_tokens,
                                max_batch=args.max_batch,
                                priorities=prios,
                                deadlines=deadlines,
                                max_queue=args.max_queue or None,
                                return_scheduler=True)
    else:
        outs = eng.serve_static(prompts, args.max_new_tokens,
                                max_batch=args.max_batch)
        sched = None
    dt = time.perf_counter() - t0
    shed = [o for o in outs if not isinstance(o, list)]
    n_tok = sum(len(o) for o in outs if isinstance(o, list))
    occ = (f", occupancy {sched.stats.mean_occupancy:.2f} over "
           f"{sched.stats.chunks} chunks" if sched is not None else "")
    if sched is not None and args.prefill_chunk:
        occ += (f", {sched.stats.prefill_forwards} chunked-prefill launches "
                f"({sched.stats.prefill_tokens} prompt tokens)")
    print(f"[serve] {mode}: {len(prompts)} requests, {n_tok} "
          f"tokens in {dt:.2f}s ({n_tok / dt:.1f} tok/s){occ}; "
          f"cache/request ≈ "
          f"{eng.cache_bytes(args.max_batch) // args.max_batch} B")
    if sched is not None:
        print(f"[serve] {sched.stats.counters_line()}")
    for o in shed:
        print(f"  req{o.rid} SHED at tick {o.tick}: {o.reason} "
              f"(priority {o.priority})")
    for i, o in enumerate(outs[:4]):
        if isinstance(o, list):
            print(f"  req{i} ({len(prompts[i])} prompt toks) -> {o[:10]}")
    if telemetry is not None and args.trace_out:
        telemetry.export_trace(args.trace_out,
                               metadata={"arch": args.arch,
                                         "scheduler": mode})
        print(f"[serve] trace -> {args.trace_out} "
              "(load at https://ui.perfetto.dev)")
    if telemetry is not None and args.metrics_out:
        telemetry.export_metrics_jsonl(args.metrics_out)
        print(f"[serve] metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
