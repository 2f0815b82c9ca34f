#!/usr/bin/env python3
"""Run the serving and training paths once on a TPU, at published widths.

    python chip_smoke.py              # one chip: phases 1-4
    python chip_smoke.py --chips 4    # four chips: the tensor-parallel paths

One process drives every phase (a chip belongs to one process at a time);
nothing here starts a subprocess.

1. device  — refuses to run unless JAX's first device is a TPU.
2. serve   — qwen3-8b at its published widths with seeded random bf16
             weights, depth cut to SERVE_LAYERS of 36. `ServingEngine.serve`
             runs mixed-length requests through continuous batching and
             chunked prefill, once on the dense pool and once on the paged
             int8 pool. The engine's logits (chunked prefill, the prompt's
             sub-block remainder, then decode steps) are compared with the
             `reference` backend's full forward: tightly in float32 at
             PARITY_LAYERS, and for the served bf16 weights within bf16
             drift.
3. train   — `Trainer` on linformer-paper at its published size (12 layers,
             d 768, n=512, k=128, MLM) for a few steps; losses must be finite.
4. kernels — the fused blockwise-causal forward and backward
             (`backward_impl="fused"`, the default causal training path) at
             qwen3-8b widths and one long S, against the fp32 reference.

With --chips 4 only the multi-chip path runs: the serving engine with
chunked prefill on a tp=4 mesh and one sharded train step, each compared
with the same work on one device in this process.

Every jitted step a phase runs must hold a Mosaic kernel (`tpu_custom_call`
in its HLO): nothing on this path interprets or falls back to the
reference. Any failed check raises, so the process exits non-zero before
the last line. The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Weights and data are generated from --seed; REPRO_TUNING_PATH is ignored.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))

SERVE_ARCH = "qwen3-8b"
SERVE_LAYERS = 8            # of 36; widths untouched
MAX_SEQ = 32768             # M = (32768/256)·16 = 2048 slots ≤ MAX_PINNED_SLOTS
PREFILL_CHUNK = 512         # two 256-token attention blocks per chunk
DECODE_CHUNK = 8
MAX_BATCH = 4
# (prompt tokens, new tokens): more requests than slots, so slots recycle.
# A prompt's sub-block remainder runs as that many unrolled decode steps in
# one program per remainder length, so every remainder here is 0 or 1.
REQUESTS = ((1281, 12), (513, 8), (2049, 16), (257, 8), (769, 12), (1024, 8))
# 4 full blocks + 1 remainder token, then 4 decode steps. The reference
# runs the whole 1280-token sequence; causality makes its logits at these
# positions those of the 1029-token prefix.
PARITY_PROMPT = 1025
PARITY_DECODE = 4
PARITY_SEQ = 1280
# Logits are compared by the relative L2 error of each position.
# The float32 check runs the same widths at PARITY_LAYERS, engine and
# reference both at "highest" matmul precision: on the CPU they agree to
# 3e-5 at 8 layers. The Mosaic kernels' own f32 matmuls may take bf16
# passes (each ≤ 2^-9), so 1e-2 leaves room for those, while bf16
# activations fail it (2e-2 measured at 2 layers on the CPU).
PARITY_LAYERS = 2
PARITY_TOL = 1e-2
# The served bf16 model against the bf16 reference: rounding differences
# between two bf16 orders of operations grow through random layers; at
# these widths and 8 layers the CPU measured 0.06-0.07 for the reference
# backend's own engine and 0.12-0.15 for the fused one. A wrong cache or
# mask gives O(1).
DRIFT_TOL = 0.3
PAGED = {"cache_format": "paged", "page_dtype": "int8"}
# The paged pool stores the cache as int8 with one fp32 scale per slot:
# each value rounds by up to half a step of amax/127 (≤ 0.4% of the
# slot's largest value), and the two random layers amplify it like the
# bf16 drift above: the CPU measured 0.03-0.047 at these widths.
PAGED_TOL = 0.1

TRAIN_ARCH = "linformer-paper"
TRAIN_SEQ = 512
TRAIN_BATCH = 32
TRAIN_STEPS = 3

KERNEL_SEQ = 16384          # M = (16384/256)·16 = 1024 slots
# Relative L2 error of the bf16 kernels' output and gradients against the
# fp32 reference on the same bf16 inputs: the kernels round probabilities
# and the compressed-slot cotangents to bf16 (2^-9 each) before matmuls;
# the CPU measured 2e-3 to 3.3e-3 at S=1024 and S=4096.
KERNEL_TOL = 1e-2

CKPT_DIR = os.path.join(ROOT, ".chip_smoke_ckpt")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_kernels(name: str, jitted, *args) -> None:
    """The jitted step's HLO must hold a Mosaic kernel call."""
    text = jitted.lower(*args).as_text()
    check("tpu_custom_call" in text, f"{name}: no tpu_custom_call in its HLO")
    print(f"[hlo] {name}: tpu_custom_call present")


def rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def report_memory(phase: str) -> None:
    import jax
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"[memory] after {phase}: device {d.id} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')}")


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------


def device_phase(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    check(d.platform == "tpu", f"first device is {d.platform!r}, not a TPU")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# 2. serve
# ---------------------------------------------------------------------------


def serve_config(layers: int = SERVE_LAYERS, dtype: str = "bfloat16"):
    import dataclasses
    from repro.configs import get_config
    cfg = get_config(SERVE_ARCH)
    print(f"[serve] {SERVE_ARCH}: {layers} of {cfg.num_layers} layers, "
          f"d_model {cfg.d_model}, heads {cfg.attention.num_heads}/"
          f"{cfg.attention.num_kv_heads}, head_dim {cfg.attention.head_dim}, "
          f"d_ff {cfg.mlp.d_ff}, vocab {cfg.vocab_size}, c="
          f"{cfg.attention.linformer.block_size} r="
          f"{cfg.attention.linformer.block_slots}, {dtype}")
    return dataclasses.replace(cfg, num_layers=layers, dtype=dtype)


def init_params(cfg, seed: int):
    import jax
    from repro.models import model as M
    return jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def make_engine(params, cfg, *, ctx=None, **kw):
    from repro.serving import ServingEngine
    return ServingEngine(params, cfg, max_seq=MAX_SEQ, ctx=ctx,
                         decode_chunk=DECODE_CHUNK,
                         prefill_chunk=PREFILL_CHUNK, **kw)


def engine_logits(eng, seq, prompt_len: int):
    """Logits at positions prompt_len-1 .. prompt_len+PARITY_DECODE-1
    through the slot pool `serve` uses: the prompt's full blocks in prefill
    chunks, its sub-block remainder and each later token through decode
    steps."""
    import numpy as np
    from repro.serving.scheduler import Request, SlotPool
    c = eng.cfg.attention.linformer.block_size
    P = eng.prefill_chunk
    seq = seq[:prompt_len + PARITY_DECODE]
    pool = SlotPool(eng, MAX_BATCH)
    pool.begin_prefill(0, Request(rid=0, tokens=tuple(seq[:prompt_len]),
                                  max_new_tokens=PARITY_DECODE))
    check(pool.ensure_row_pages(0, len(seq)), "no pages for the parity row")
    nfull = (prompt_len // c) * c
    for s in range(0, nfull, P):
        n = min(P, nfull - s)
        toks = np.zeros((1, P), np.int32)
        toks[0, :n] = seq[s:s + n]
        logits = pool.prefill_chunk_rows([0], toks, np.asarray([n]))
    out = []
    if prompt_len > nfull:
        logits = pool.prefill_remainder_rows(
            [0], np.asarray([seq[nfull:prompt_len]], np.int32))
    out.append(logits[0])
    for t in range(prompt_len, len(seq)):
        logits = pool.prefill_remainder_rows(
            [0], np.asarray([seq[t:t + 1]], np.int32))
        out.append(logits[0])
    return np.stack(out).astype(np.float32)


def reference_logits(params, cfg, seq, prompt_len: int):
    """The `reference` backend's full forward over `seq`, at the positions
    `engine_logits` returns (a block-multiple length: the causal form
    folds whole blocks)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    ref_cfg = cfg.with_attention_backend("reference")
    fwd = jax.jit(lambda p, t: M.forward(p, ref_cfg, {"tokens": t})[0])
    logits = fwd(params, jnp.asarray(np.asarray(seq, np.int32)[None]))
    return np.asarray(logits[0, prompt_len - 1:prompt_len + PARITY_DECODE],
                      np.float32)


def compare_logits(name: str, got, want, tol: float) -> None:
    import numpy as np
    errs = [rel_err(g, w) for g, w in zip(got, want)]
    check(bool(np.isfinite(got).all()), f"{name}: non-finite logits")
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    print(f"[serve] {name} logits vs reference: rel-L2 per position "
          f"{[round(e, 6) for e in errs]} (tol {tol}), max-abs "
          f"{float(np.abs(got - want).max()):.5f}, argmax agrees "
          f"{agree}/{len(errs)}")
    check(max(errs) <= tol, f"{name}: logits off the reference")


def float32_parity(seed: int, seq, engines) -> None:
    """The served widths in float32 at PARITY_LAYERS, engine and reference
    at "highest" matmul precision: the tight check of the cache path and
    kernels. `engines` maps a name to (ParallelCtx or None, engine
    keywords, tolerance)."""
    import jax
    import jax.numpy as jnp
    cfg = serve_config(PARITY_LAYERS, "float32")
    params = init_params(cfg, seed)
    with jax.default_matmul_precision("highest"):
        want = reference_logits(params, cfg, seq, PARITY_PROMPT)
        for name, (ctx, kw, tol) in engines.items():
            p = params if ctx is None else placed(params, ctx)
            eng = make_engine(p, cfg, ctx=ctx, cache_dtype=jnp.float32, **kw)
            compare_logits(f"float32 {PARITY_LAYERS}-layer {name}",
                           engine_logits(eng, seq, PARITY_PROMPT), want, tol)
    del params, p, eng
    gc.collect()      # the engine's jits hold it in a cycle with its params


def check_engine_kernels(name: str, eng) -> None:
    """HLO of the three steps `serve` runs: prefill chunk, remainder (decode
    steps) and the decode scan."""
    import jax
    import jax.numpy as jnp
    pool = eng.init_pool_cache(MAX_BATCH)
    rows = jnp.arange(MAX_BATCH, dtype=jnp.int32)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    require_kernels(f"{name} prefill chunk", eng._pool_prefill_chunk,
                    eng.params, pool, i32(MAX_BATCH, eng.prefill_chunk),
                    i32(MAX_BATCH), rows)
    require_kernels(f"{name} prefill remainder",
                    eng._pool_prefill_remainder, eng.params, pool,
                    i32(MAX_BATCH, 1), rows)
    require_kernels(f"{name} decode scan", eng.pool_chunk_fn(DECODE_CHUNK),
                    eng.params, i32(MAX_BATCH),
                    jnp.zeros((MAX_BATCH,), bool), pool,
                    jax.random.PRNGKey(0))


def serve_requests(eng, prompts, budgets, name: str):
    outs, sched = eng.serve(prompts, budgets, max_batch=MAX_BATCH,
                            return_scheduler=True)
    got = [len(o) if isinstance(o, list) else None for o in outs]
    print(f"[serve] {name}: {len(prompts)} requests, tokens per request "
          f"{got} of {list(budgets)}, sheds={sched.stats.sheds}, "
          f"prefill launches={sched.stats.prefill_forwards}, "
          f"decode chunks={sched.stats.chunks}")
    check(sched.stats.sheds == 0 and got == list(budgets),
          f"{name}: a request was shed or cut short")
    return outs


def make_requests(vocab: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(4, vocab, n).tolist() for n, _ in REQUESTS]
    parity_seq = rng.integers(4, vocab, PARITY_SEQ)
    return prompts, [b for _, b in REQUESTS], parity_seq.tolist()


def token_agreement(a, b) -> str:
    same = sum(x == y for oa, ob in zip(a, b) for x, y in zip(oa, ob))
    return f"{same}/{sum(len(o) for o in a)}"


def serve_phase(seed: int) -> None:
    cfg = serve_config()
    prompts, budgets, seq = make_requests(cfg.vocab_size, seed)
    float32_parity(seed, seq, {
        "dense": (None, {}, PARITY_TOL),
        "paged int8": (None, PAGED, PAGED_TOL)})

    params = init_params(cfg, seed)
    dense = make_engine(params, cfg)
    check_engine_kernels("dense", dense)
    compare_logits("bf16 dense", engine_logits(dense, seq, PARITY_PROMPT),
                   reference_logits(params, cfg, seq, PARITY_PROMPT),
                   DRIFT_TOL)
    out_dense = serve_requests(dense, prompts, budgets, "dense pool")

    paged = make_engine(params, cfg, **PAGED)
    check_engine_kernels("paged int8", paged)
    out_paged = serve_requests(paged, prompts, budgets, "paged int8 pool")
    print(f"[serve] paged int8 tokens equal to dense: "
          f"{token_agreement(out_paged, out_dense)} (int8 pages round the "
          f"cache: a near-tie argmax may flip, and the continuation after "
          f"it differs)")
    report_memory("serve")


# ---------------------------------------------------------------------------
# 3. train
# ---------------------------------------------------------------------------


def train_config():
    from repro.configs import get_config
    cfg = get_config(TRAIN_ARCH)
    a = cfg.attention
    print(f"[train] {TRAIN_ARCH}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {a.num_heads}, n={TRAIN_SEQ}, "
          f"k={a.linformer.k}, vocab {cfg.vocab_size}, {cfg.objective}, "
          f"{cfg.dtype}, batch {TRAIN_BATCH}")
    return cfg


def run_trainer(cfg, seed: int, steps: int, *, ctx=None, label: str):
    """`steps` Trainer steps from scratch; returns (trainer, per-step
    records of loss and grad norm)."""
    import jax
    from repro.configs.base import OptimizerConfig, TrainConfig
    from repro.models import model as M
    from repro.telemetry import Telemetry
    from repro.train import Trainer
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    tcfg = TrainConfig(
        seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH, steps=steps,
        log_every=1, checkpoint_every=10**9, checkpoint_dir=CKPT_DIR,
        seed=seed, optimizer=OptimizerConfig(warmup_steps=1,
                                             total_steps=steps))
    tel = Telemetry()
    # the trainer's own log lines carry step times: print records instead
    trainer = Trainer(cfg, tcfg, ctx=ctx, telemetry=tel,
                      log_fn=lambda s: None)
    try:
        trainer.run()
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    steps_rec = [{k: r[k] for k in ("step", "loss", "grad_norm")}
                 for r in tel.records if r["kind"] == "train_step"]
    print(f"[train] {label}: {steps_rec}")
    shapes = jax.eval_shape(lambda: trainer.init_state()[:2])
    batch = M.make_train_batch_shapes(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ)
    require_kernels(f"{label} train step", trainer.train_step, *shapes,
                    batch)
    return trainer, steps_rec


def train_phase(seed: int) -> None:
    import math
    cfg = train_config()
    _, recs = run_trainer(cfg, seed, TRAIN_STEPS, label="one chip")
    losses = [r["loss"] for r in recs]
    check(len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)),
          "training losses missing or not finite")
    report_memory("train")


# ---------------------------------------------------------------------------
# 4. kernels
# ---------------------------------------------------------------------------


def kernel_phase(seed: int, S: int = KERNEL_SEQ) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.causal import blockwise_causal_attention_chunked
    from repro.kernels import ops
    a = get_config(SERVE_ARCH).attention
    H, Hkv, Dh = a.num_heads, a.num_kv_heads, a.head_dim
    c, r = a.linformer.block_size, a.linformer.block_slots
    scale = Dh ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (1, S, H, Dh), bf)
    k = jax.random.normal(ks[1], (1, S, Hkv, Dh), bf)
    v = jax.random.normal(ks[2], (1, S, Hkv, Dh), bf)
    E = (jax.random.normal(ks[3], (c, r)) * c ** -0.5).astype(bf)
    F = (jax.random.normal(ks[4], (c, r)) * c ** -0.5).astype(bf)
    do = jax.random.normal(ks[5], (1, S, H, Dh), bf)

    def fused(*xs):
        return ops.fused_blockwise_causal_attention(
            *xs, block_size=c, block_slots=r, scale=scale,
            backward_impl="fused")

    def reference(*xs):
        return blockwise_causal_attention_chunked(
            *xs, block_size=c, scale=scale)

    def fwd_bwd(fn, xs, g):
        out, vjp = jax.vjp(fn, *xs)
        return (out,) + tuple(vjp(g))

    xs = (q, k, v, E, F)
    step = jax.jit(lambda xs, g: fwd_bwd(fused, xs, g))
    require_kernels("blockwise-causal fwd+bwd", step, xs, do)
    got = step(xs, do)
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    # a float32 matmul on the TPU runs in bf16 passes unless asked not to
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda xs, g: fwd_bwd(reference, xs, g))(f32(xs),
                                                                f32(do))
    names = ("out", "dq", "dk", "dv", "dE", "dF")
    errs = {n: rel_err(x, y) for n, x, y in zip(names, got, want)}
    print(f"[kernels] blockwise-causal S={S} H={H} Hkv={Hkv} Dh={Dh} c={c} "
          f"r={r} M={(S // c) * r}: rel-L2 vs fp32 reference "
          f"{ {n: round(e, 5) for n, e in errs.items()} } (tol {KERNEL_TOL})")
    check(max(errs.values()) <= KERNEL_TOL,
          "fused blockwise-causal kernels off the reference")
    report_memory("kernels")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def placed(params, ctx):
    import jax
    from repro.parallel.sharding import param_shardings
    return jax.tree.map(jax.device_put, params, param_shardings(params, ctx))


def tp_serve_phase(seed: int, tp: int = 4) -> None:
    from repro.launch.mesh import make_local_mesh
    from repro.parallel.sharding import ParallelCtx
    cfg = serve_config()
    prompts, budgets, seq = make_requests(cfg.vocab_size, seed)
    mesh = make_local_mesh(model_shards=tp)
    ctx = ParallelCtx(mesh=mesh)
    with mesh:
        float32_parity(seed, seq, {
            "one device": (None, {}, PARITY_TOL),
            f"tp={tp}": (ctx, {}, PARITY_TOL)})

    params = init_params(cfg, seed)
    want = reference_logits(params, cfg, seq, PARITY_PROMPT)
    one = make_engine(params, cfg)
    got_one = engine_logits(one, seq, PARITY_PROMPT)
    compare_logits("bf16 one device", got_one, want, DRIFT_TOL)
    out_one = serve_requests(one, prompts, budgets, "one device")
    with mesh:
        eng = make_engine(placed(params, ctx), cfg, ctx=ctx)
        check(eng.plan.tp == tp, f"plan did not shard attention tp={tp}")
        check_engine_kernels(f"tp={tp}", eng)
        got_tp = engine_logits(eng, seq, PARITY_PROMPT)
        compare_logits(f"bf16 tp={tp}", got_tp, want, DRIFT_TOL)
        print(f"[serve] bf16 tp={tp} vs one device: rel-L2 per position "
              f"{[round(rel_err(a, b), 6) for a, b in zip(got_tp, got_one)]}")
        out_tp = serve_requests(eng, prompts, budgets, f"tp={tp} mesh")
    print(f"[serve] tp={tp} tokens equal to one device: "
          f"{token_agreement(out_tp, out_one)}")
    report_memory(f"tp={tp} serve")


def sharded_train_phase(seed: int, steps: int = 2) -> None:
    from repro.launch.mesh import make_local_mesh
    from repro.parallel.sharding import ParallelCtx
    cfg = train_config()
    _, one = run_trainer(cfg, seed, steps, label="one device")
    mesh = make_local_mesh(model_shards=2)
    ctx = ParallelCtx(mesh=mesh)
    with mesh:
        trainer, sharded = run_trainer(cfg, seed, steps, ctx=ctx,
                                       label="data=2 x model=2")
    check(trainer.plan.tp == 2, "plan did not shard attention tp=2")
    for key in ("loss", "grad_norm"):
        a = [r[key] for r in one]
        b = [r[key] for r in sharded]
        errs = [abs(x - y) / abs(y) for x, y in zip(a, b)]
        print(f"[train] {key}: one device {a}, mesh {b}, rel diff "
              f"{[round(e, 6) for e in errs]} (tol 1e-2)")
        check(len(a) == len(b) == steps and max(errs) <= 1e-2,
              f"sharded train step {key} differs from one device")
    report_memory("sharded train")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    os.environ.pop("REPRO_TUNING_PATH", None)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    device = device_phase(args.chips)
    print(f"[cache] compilation cache: {enable_compile_cache()}")
    if args.chips == 1:
        serve_phase(args.seed)
        train_phase(args.seed)
        kernel_phase(args.seed)
    else:
        tp_serve_phase(args.seed)
        sharded_train_phase(args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
