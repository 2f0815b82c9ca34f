"""Multi-pod dry-run: lower + compile every (architecture × input-shape) cell
on the production mesh, WITHOUT allocating real tensors, and extract the
roofline terms from the compiled artifact. It runs on the CPU with 512 host
devices standing in for the chips, set only when run as a script (the
device count locks at the first jax import); importing it changes nothing.

  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --arch qwen3-8b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --all            # every cell, both meshes

Per cell this prints/records:
  * compiled.memory_analysis()  — per-device bytes (proves it fits / doesn't)
  * compiled.cost_analysis()    — HLO FLOPs + bytes accessed
  * collective bytes parsed from the post-SPMD HLO, by collective kind
  * the three roofline terms (compute / memory / collective, seconds)

Artifacts land in benchmarks/artifacts/dryrun/<cell>.json and are consumed by
benchmarks/roofline.py and EXPERIMENTS.md.
"""
import os

if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512")))

import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, SHAPES_BY_NAME, get_config)
from repro.configs.base import OptimizerConfig, ShapeConfig
from repro.launch import mesh as mesh_lib
from repro.launch.specs import as_named, batch_specs, input_specs
from repro.models import model as model_lib
from repro.optim import adamw_init
from repro.parallel.sharding import ParallelCtx, param_shardings
from repro.train.trainer import make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts", "dryrun")

def build_step(arch: str, cfg, shape: ShapeConfig, ctx: ParallelCtx,
               microbatch: int = 0):
    """Returns (step_fn, abstract_args tuple, in_shardings tuple)."""
    mesh = ctx.mesh
    rng = jax.random.PRNGKey(0)

    params_abs = jax.eval_shape(lambda: model_lib.init_params(rng, cfg))
    p_sh = param_shardings(params_abs, ctx)

    if shape.kind == "train":
        opt_abs = jax.eval_shape(
            lambda: adamw_init(params_abs, OptimizerConfig()))
        from jax.sharding import NamedSharding, PartitionSpec
        o_sh = {"mu": param_shardings(opt_abs["mu"], ctx),
                "nu": param_shardings(opt_abs["nu"], ctx),
                "step": NamedSharding(mesh, PartitionSpec())
                if mesh else None}
        batch_abs = input_specs(cfg, shape)
        b_sh = as_named(batch_specs(cfg, shape, ctx), mesh)
        step = make_train_step(cfg, OptimizerConfig(), ctx=ctx,
                               microbatch=microbatch)
        return step, (params_abs, opt_abs, batch_abs), (p_sh, o_sh, b_sh)

    if shape.kind == "prefill":
        batch_abs = input_specs(cfg, shape)
        b_sh = as_named(batch_specs(cfg, shape, ctx), mesh)

        def prefill_step(params, batch):
            logits, aux, cache = model_lib.forward(
                params, cfg, batch, ctx=ctx, return_cache=True,
                cache_max_seq=shape.seq_len)
            return logits, cache

        return prefill_step, (params_abs, batch_abs), (p_sh, b_sh)

    # decode
    tree = input_specs(cfg, shape)
    sh = as_named(batch_specs(cfg, shape, ctx), mesh)

    def serve_step(params, batch_t, cache):
        return model_lib.decode_step(params, cfg, batch_t, cache, ctx=ctx)

    return serve_step, (params_abs, tree["batch_t"], tree["cache"]), \
        (p_sh, sh["batch_t"], sh["cache"])


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             attention: Optional[str] = None,
             remat: Optional[str] = None,
             fsdp: Optional[str] = None,
             moe_overrides: Optional[Dict] = None,
             lin_overrides: Optional[Dict] = None,
             model_overrides: Optional[Dict] = None,
             microbatch: int = 0,
             extra_tag: str = "",
             out_dir: str = ARTIFACT_DIR) -> Dict:
    shape = SHAPES_BY_NAME[shape_name]
    cfg = get_config(arch)
    if attention and cfg.family != "ssm":
        cfg = cfg.with_attention_kind(attention)
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if model_overrides:
        mo = dict(model_overrides)
        ssm_chunk = mo.pop("_ssm_chunk", None)
        if mo:
            cfg = dataclasses.replace(cfg, **mo)
        if ssm_chunk:
            cfg = dataclasses.replace(
                cfg, ssm=dataclasses.replace(cfg.ssm, chunk_size=ssm_chunk))
    if moe_overrides:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, **moe_overrides))
    if lin_overrides:
        cfg = dataclasses.replace(cfg, attention=dataclasses.replace(
            cfg.attention, linformer=dataclasses.replace(
                cfg.attention.linformer, **lin_overrides)))
    kind = cfg.attention.kind if cfg.family != "ssm" else "native"

    # skip rules (DESIGN.md §5.1): full attention at 524288 is not runnable
    if shape.name == "long_500k" and kind == "standard":
        return {"arch": arch, "shape": shape_name, "skipped":
                "pure full attention at 500k (O(n^2) / 21-214GB KV per seq)"}

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    ctx = ParallelCtx(mesh=mesh,
                      fsdp=fsdp if fsdp is not None
                      else mesh_lib.fsdp_for(arch, multi_pod))

    t0 = time.time()
    step, args, shardings = build_step(arch, cfg, shape, ctx,
                                       microbatch=microbatch)
    with mesh:
        lowered = jax.jit(step, in_shardings=shardings).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes":
                int(getattr(mem, "generated_code_size_in_bytes", 0)),
        }
        mem_d["total_bytes"] = sum(v for k, v in mem_d.items()
                                   if k != "generated_code_bytes")
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}

    try:
        cost = compiled.cost_analysis()
        xla_flops = float(cost.get("flops", 0.0))
        xla_bytes = float(cost.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        xla_flops, xla_bytes = 0.0, 0.0

    # Trip-count-aware analysis (XLA's cost_analysis counts while bodies once
    # — ~L× undercount for scanned layers). See launch/hlo_cost.py.
    from repro.launch import hlo_cost
    hlo = compiled.as_text()
    a = hlo_cost.analyze_text(hlo)
    flops = a["flops"]
    # memory term: geometric mean of the perfect-fusion lower bound and the
    # op-boundary upper bound — TPU fusion lands between the two.
    bytes_min = a["bytes_min"]
    bytes_upper = a["bytes"]
    bytes_accessed = (max(bytes_min, 1.0) * max(bytes_upper, 1.0)) ** 0.5
    coll = a["collectives"]
    coll_total = a["collective_bytes"]

    chips = mesh.devices.size
    # cost_analysis flops/bytes are per-device for SPMD-partitioned modules.
    roofline = {
        "compute_s": flops / mesh_lib.PEAK_FLOPS_BF16,
        "memory_s": bytes_accessed / mesh_lib.HBM_BW,
        "collective_s": coll_total / mesh_lib.ICI_BW,
    }
    dom = max(roofline, key=roofline.get)

    n_params = cfg.param_count_estimate
    n_active = cfg.active_param_count_estimate
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                   else (shape.seq_len if shape.kind ==
                                         "prefill" else 1))
    mult = 6 if shape.kind == "train" else 2
    model_flops_global = mult * n_active * tokens
    model_flops_per_chip = model_flops_global / chips

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "attention_kind": kind,
        "fsdp": ctx.fsdp,
        "remat": cfg.remat,
        "tag": extra_tag,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": flops,
        "bytes_accessed_per_device": bytes_accessed,
        "bytes_lower_per_device": bytes_min,
        "bytes_upper_per_device": bytes_upper,
        "xla_cost_analysis": {"flops": xla_flops, "bytes": xla_bytes,
                              "note": "while bodies counted once"},
        "hlo_cost_warnings": a["warnings"],
        "collectives": coll,
        "collective_bytes_per_device": coll_total,
        "memory": mem_d,
        "roofline": roofline,
        "dominant": dom,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": (model_flops_per_chip / flops) if flops else 0.0,
        "tokens": tokens,
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"-{extra_tag}" if extra_tag else ""
        name = f"{arch}-{shape_name}-{rec['mesh']}-{kind}{tag}"
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        # keep the post-SPMD HLO for offline re-analysis (hlo_cost tweaks
        # shouldn't require recompiling 80 cells)
        import gzip
        with gzip.open(os.path.join(out_dir, name + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--attention", default=None,
                    help="override attention kind (standard baseline)")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--fsdp", default=None,
                    help="override FSDP policy: none|data|pod_data")
    ap.add_argument("--capacity-floor-one", action="store_true")
    ap.add_argument("--weight-stationary", action="store_true")
    ap.add_argument("--block-slots", type=int, default=None)
    ap.add_argument("--block-size", type=int, default=None)
    ap.add_argument("--single-pass-cache", action="store_true")
    ap.add_argument("--seq-shard-acts", action="store_true")
    ap.add_argument("--chunked-ce", type=int, default=0)
    ap.add_argument("--ssm-chunk", type=int, default=0)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--tag", default="")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    moe_ov = {}
    if args.capacity_floor_one:
        moe_ov["capacity_floor_one"] = True
    if args.weight_stationary:
        moe_ov["weight_stationary_decode"] = True
    lin_ov = {}
    if args.block_slots:
        lin_ov["block_slots"] = args.block_slots
    if args.block_size:
        lin_ov["block_size"] = args.block_size
    model_ov = {}
    if args.single_pass_cache:
        model_ov["single_pass_cache"] = True
    if args.seq_shard_acts:
        model_ov["seq_shard_activations"] = True
    if args.chunked_ce:
        model_ov["chunked_ce"] = args.chunked_ce
    if args.ssm_chunk:
        from repro.configs.base import SSMConfig
        import dataclasses as _dc
        # applied in run_cell via a nested replace
        model_ov["_ssm_chunk"] = args.ssm_chunk

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES_BY_NAME:
                for mp in (False, True):
                    cells.append((arch, shape, mp))
    else:
        assert args.arch and args.shape
        cells.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for arch, shape, mp in cells:
        label = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
        try:
            rec = run_cell(arch, shape, multi_pod=mp,
                           attention=args.attention, remat=args.remat,
                           fsdp=args.fsdp, moe_overrides=moe_ov or None,
                           lin_overrides=lin_ov or None,
                           model_overrides=model_ov or None,
                           microbatch=args.microbatch,
                           extra_tag=args.tag)
            if "skipped" in rec:
                print(f"[dryrun] SKIP {label}: {rec['skipped']}")
                continue
            r = rec["roofline"]
            print(f"[dryrun] OK   {label} compile={rec['compile_s']}s "
                  f"flops/dev={rec['flops_per_device']:.3e} "
                  f"mem/dev={rec['memory'].get('total_bytes', 0)/2**30:.2f}GiB "
                  f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                  f"coll={r['collective_s']:.4f}s dom={rec['dominant']}")
        except Exception:
            failures += 1
            print(f"[dryrun] FAIL {label}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
