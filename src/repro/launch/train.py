"""Production training launcher.

    python -m repro.launch.train --arch qwen3-8b --smoke --steps 50
    python -m repro.launch.train --arch qwen3-8b --shape train_4k \
        --mesh single_pod            # on a real v5e pod slice

On multi-host TPU, initialize with --coordinator/--num-processes/--process-id
(jax.distributed); this container runs the --smoke path on CPU.
"""
import argparse
import dataclasses
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--attention", default=None,
                    help="override attention kind: standard|linformer_causal")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single_pod", "multi_pod", "local"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    args = ap.parse_args()

    if args.coordinator:
        import jax
        jax.distributed.initialize(args.coordinator, args.num_processes,
                                   args.process_id)

    from repro.configs import SHAPES_BY_NAME, get_config, get_smoke_config
    from repro.configs.base import OptimizerConfig, TrainConfig
    from repro.launch import mesh as mesh_lib
    from repro.launch.compile_cache import enable_compile_cache
    from repro.parallel.sharding import ParallelCtx
    from repro.train import Trainer

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg, dtype="float32")
    if args.attention and cfg.family != "ssm":
        cfg = cfg.with_attention_kind(args.attention)

    shape = SHAPES_BY_NAME[args.shape]
    seq = args.seq or (64 if args.smoke else shape.seq_len)
    batch = args.batch or (8 if args.smoke else shape.global_batch)

    ctx = None
    if args.mesh != "none":
        if args.mesh == "local":
            m = mesh_lib.make_local_mesh()
        else:
            m = mesh_lib.make_production_mesh(
                multi_pod=args.mesh == "multi_pod")
        ctx = ParallelCtx(mesh=m, fsdp=mesh_lib.fsdp_for(
            args.arch, args.mesh == "multi_pod"))

    tcfg = TrainConfig(
        seq_len=seq, global_batch=batch, microbatch=args.microbatch,
        steps=args.steps, log_every=max(args.steps // 20, 1),
        checkpoint_every=max(args.steps // 4, 1),
        checkpoint_dir=os.path.join(args.ckpt_dir, args.arch),
        optimizer=OptimizerConfig(lr=args.lr,
                                  warmup_steps=max(args.steps // 10, 1),
                                  total_steps=args.steps))
    trainer = Trainer(cfg, tcfg, ctx=ctx)
    metrics = trainer.run()
    print(f"[train] final: {metrics}")


if __name__ == "__main__":
    main()
