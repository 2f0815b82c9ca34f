"""Training through the program's trainer (mix kind `train`).

Set-up builds one `Trainer` (its jitted step donates parameters and
optimizer state), makes the weights from the seed and AdamW's zero state,
and drives that same step through the first `check_steps` steps on
batches that all differ. Those steps compile the step and are recorded for
the check: each step's loss, the first gradient as the optimizer got it
(its first moment after one step, over 1 − β1), and after the last of them
the parameters' change. The window then goes on stepping the same state:
each step builds its batch on the host, runs, and syncs on its loss, as
the trainer's own loop does.

Correctness, once the window has closed and the state is freed: the
configuration's float32 reference (bench/reference) repeats the first
steps from the same weights on the same batches, and the worst leaf's gap
of norms is compared for the gradient and for the change.
"""
from __future__ import annotations

import functools
import gc
import json
import os
import shutil
import time
from typing import Dict

import numpy as np

from bench import harness, reference, trace_reduce, traffic, weights, work


@functools.lru_cache(maxsize=None)
def _norm_jit():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda leaves: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in leaves]))


def _norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, keyed by its path, in one program."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = np.asarray(_norm_jit()(tuple(v for _, v in flat)))
    return {jax.tree_util.keystr(k): float(v)
            for (k, _), v in zip(flat, vals)}


def _diff_norms(a, b) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    d = jax.tree.map(lambda x, y: x.astype(jnp.float32)
                     - y.astype(jnp.float32), a, b)
    return _norms(d)


def worst_leaf(got: Dict[str, float], want: Dict[str, float],
               keep=None) -> float:
    """Largest |‖got‖ − ‖want‖| over leaves, each against the larger of
    the reference leaf's norm and the median reference leaf's."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30)
               for k in keys)


def compare(prog: Dict, ref: Dict, opt: Dict) -> Dict[str, float]:
    """The three numbers compared: loss gap over the first steps, the
    first gradient's worst leaf and the change's worst leaf. Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    rounding alone and are left out of the change."""
    g_ref = reference.leaf_norms(ref["first_grad"])
    med = float(np.median(list(g_ref.values())))
    moving = {k for k, v in g_ref.items() if v >= 1e-3 * med}
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(prog["losses"], ref["losses"]))
    return {"loss_gap": loss,
            "grad_gap": worst_leaf(prog["first_grad"], g_ref),
            "change_gap": worst_leaf(prog["change"], ref["change"], moving)}


class Run:
    """The trainer's compiled step with its state, driven from the seed."""

    def __init__(self, cell, seed: int):
        import jax
        from repro.configs.base import OptimizerConfig, TrainConfig
        from repro.models import model as M
        from repro.optim import adamw_init
        from repro.train import Trainer
        mix = cell.mix
        self.cell, self.seed, self.mix = cell, seed, mix
        self.vocab = cell.config["vocab_size"]
        mc = harness.model_config(cell.config)
        ckpt = os.path.join(harness.WORK_DIR, "ckpt")
        tcfg = TrainConfig(seq_len=mix["seq_len"], global_batch=mix["batch"],
                           seed=seed % (1 << 31), checkpoint_dir=ckpt,
                           optimizer=OptimizerConfig(**mix["optimizer"]))
        self.trainer = Trainer(mc, tcfg, log_fn=lambda s: None)
        shutil.rmtree(ckpt, ignore_errors=True)
        self.shapes = jax.eval_shape(
            lambda: M.init_params(jax.random.PRNGKey(0), mc))
        self.params = weights.make(self.shapes, seed, self.vocab)
        self.opt = adamw_init(self.params, tcfg.optimizer)
        self.steps = 0
        self.loss_tokens = 0

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        return traffic.batch(self.mix, self.seed, step, self.vocab)

    def step(self, np_batch) -> float:
        import jax.numpy as jnp
        batch = {k: jnp.asarray(v) for k, v in np_batch.items()}
        self.params, self.opt, m = self.trainer.train_step(
            self.params, self.opt, batch)
        self.steps += 1
        self.loss_tokens += int(np_batch["loss_mask"].sum())
        return float(m["loss"])

    def first_steps(self) -> Dict:
        """The checked steps through the window's own call and feed."""
        import jax
        import jax.numpy as jnp
        b1 = self.mix["optimizer"]["b1"]
        p0 = jax.tree.map(jnp.copy, self.params)
        losses, grad = [], None
        for i in range(self.mix["check_steps"]):
            losses.append(self.step(self.batch(i)))
            if grad is None:
                grad = {k: v / (1 - b1)
                        for k, v in _norms(self.opt["mu"]).items()}
        change = _diff_norms(self.params, p0)
        del p0
        return {"losses": losses, "first_grad": grad, "change": change}


def reference_run(cell, seed: int, shapes, prec=reference.FP32) -> Dict:
    mix = cell.mix
    p0 = weights.make(shapes, seed, cell.config["vocab_size"])
    batches = [traffic.batch(mix, seed, i, cell.config["vocab_size"])
               for i in range(mix["check_steps"])]
    out = reference.train_run(p0, batches, cell.config, mix["optimizer"],
                              prec)
    change = _diff_norms(out["params"], p0)
    return {"losses": out["losses"], "first_grad": out["first_grad"],
            "change": change}


def run(cell, seed: int, seconds: float, trace: bool, device: Dict,
        t_start: float) -> Dict:
    import jax
    compiles = harness.count_compiles()
    r = Run(cell, seed)
    prog = r.first_steps()
    n0 = compiles()
    setup_s = time.perf_counter() - t_start
    mix = cell.mix
    trace_dir = os.path.join(harness.WORK_DIR, f"trace-{cell.name}")
    half = min(mix["trace_seconds"], seconds) / 2
    span = (seconds / 2 - half, seconds / 2 + half)
    tracing = traced = False
    steps0, tok0 = r.steps, r.loss_tokens
    t0 = time.perf_counter()
    elapsed = 0.0
    while elapsed < seconds:
        if trace and not traced and not tracing and elapsed >= span[0]:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            ann.__enter__()
            tracing, t_steps, t_tok = True, r.steps, r.loss_tokens
        elif tracing and elapsed >= span[1]:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing, traced = False, True
            t_steps, t_tok = r.steps - t_steps, r.loss_tokens - t_tok
        if tracing:
            with jax.profiler.TraceAnnotation("host_batch"):
                b = r.batch(r.steps)
            with jax.profiler.TraceAnnotation("train_step"):
                r.step(b)
        else:
            r.step(r.batch(r.steps))
        elapsed = time.perf_counter() - t0
    if tracing:
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        t_steps, t_tok = r.steps - t_steps, r.loss_tokens - t_tok
    steps = r.steps - steps0
    compiled_in_window = compiles() - n0
    memory = harness.memory_peak_bytes(cell.chips)
    shapes = r.shapes
    del r
    gc.collect()

    t_check = time.perf_counter()
    ref = reference_run(cell, seed, shapes)
    nums = compare(prog, ref, mix["optimizer"])
    check_s = time.perf_counter() - t_check
    checks = harness.judged(nums, cell.limits)
    B, S = mix["batch"], mix["seq_len"]
    dev = dict(device, memory_peak_bytes=memory)
    notes = {"steps": steps, "window_s": round(elapsed, 3),
             "compiles_in_window": compiled_in_window,
             "check_s": round(check_s, 3),
             "loss_gap": nums["loss_gap"],
             "losses": prog["losses"], "ref_losses": ref["losses"]}
    if trace:
        red = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(trace_dir), ("host_batch", "train_step")))
        s = work.shapes_from_config(cell.config)
        pk = harness.peaks(device["kind"])
        kf, kb = work.exact_attention_kernels(s, B, S)
        record = {"trace": red, "peak": pk,
                  "work": {"train_flops": work.train_step(s, B, S, 0) * t_steps
                           + 3 * 2 * t_tok * s.head_params,
                           "exact_kernel_roofline_s":
                               work.roofline_s(kf, kb, pk) * t_steps}}
        metrics = harness.per_layer_values(cell, record)
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {"train_tokens_per_s": {"value": steps * B * S / elapsed,
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        breakdown = None
    return dict(correct=harness.checks_ok(checks), attempted=steps,
                failed=0, metrics=metrics, device=dev, checks=checks,
                breakdown=breakdown, notes=notes)


def _line(kind: str, seed: int, nums: Dict, limits: Dict, **kw) -> None:
    checks = harness.judged(nums, limits)
    print(json.dumps(harness.plain(dict(
        kind=kind, seed=seed, correct=harness.checks_ok(checks),
        checks=checks, **nums, **kw)), allow_nan=False), flush=True)


def calibrate(cell, seeds, control_seeds, seconds, sweep, device) -> None:
    """Readings for bench.calibrate, one JSON line each: per seed the
    numbers compared for the program; on the control seeds, for the fp8
    reference in the program's place and for the program with half of each
    batch left out (a fault the check has to catch). Every line holds its
    numbers to the cell's limits under `checks` and says `correct`."""
    opt = cell.mix["optimizer"]
    for seed in seeds:
        r = Run(cell, seed)
        prog = r.first_steps()
        shapes = r.shapes
        del r
        gc.collect()
        ref = reference_run(cell, seed, shapes)
        _line("program", seed, compare(prog, ref, opt), cell.limits,
              losses=prog["losses"], ref_losses=ref["losses"])
        if seed not in control_seeds:
            continue
        low = reference_run(cell, seed, shapes, reference.FP8)
        low["first_grad"] = reference.leaf_norms(low["first_grad"])
        _line("control", seed, compare(low, ref, opt), cell.limits)
        r = Run(cell, seed)
        step = r.trainer.train_step
        n = cell.mix["batch"] // 2
        r.trainer.train_step = lambda p, o, b: step(
            p, o, {k: v[:n] for k, v in b.items()})
        half = r.first_steps()
        del r
        gc.collect()
        _line("half_batch", seed, compare(half, ref, opt), cell.limits)
