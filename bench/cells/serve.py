"""Open-loop serving through the program's scheduler (mix kind `serve`).

Set-up makes the weights from the seed, builds one `ServingEngine` on the
dense pool and warms every program the mix uses (a prefill chunk, one
remainder program per remainder length, the decode chunk) with one request
per remainder length. A cell on several chips runs all of it, the window
and the check too, under one mesh that shards the model over every chip
(tensor parallelism, `model` = chips): each chip draws its own shard of the
weights, laid out by the program's sharding rules, and the engine is given
the mesh. On one chip there is no mesh.

The window then drives `Scheduler.run` over a fresh `SlotPool`. Each
request is submitted when it is due: the scheduler reports every phase
through its telemetry spans, and on leaving each span the benchmark
submits whatever has come due (so admission happens at the granularity of
the scheduler's rounds, as in a polling server). Tokens are stamped on the
host clock as the scheduler streams them. Arrivals stop at `--seconds`;
the requests already due are then drained, for at most the mix's
`drain_limit_s`.

Correctness: once the window has closed and the pool is freed, a sample of
the finished requests drawn from the seed, always with the longest one, is
run through the configuration's float32 reference (bench/reference) over
its prompt and served tokens; `gap_max` is the widest gap by which a served
token's reference logit lies below the reference's best (+inf for an id
outside the vocabulary).

In a traced run the benchmark counts the work of each scheduler span from
the pool's slots, as the scheduler chooses its rows; the count is held
against the rows and tokens that the span itself reports, and against the
decode-chunk programs that each chip ran in the trace, and the run fails
where they differ.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import time
from typing import Dict, List

import numpy as np

from bench import harness, reference, trace_reduce, traffic, weights, work

SPANS = ("decode_chunk", "prefill_chunk_forward", "prefill_remainder_forward")
WAIT = "waiting_for_arrivals"
DECODE_MODULE = "jit__lambda"     # the decode chunk's program in the trace


class WorkMismatch(RuntimeError):
    """The benchmark's count of a span's work disagrees with the program."""


class _DrainOver(Exception):
    pass


def _telemetry(window: "_Window"):
    """The scheduler's telemetry facade: every span it opens goes through
    `_Span`. On leaving a span, due arrivals are submitted; in a traced
    run each span is also a profiler annotation and its work is counted."""
    from repro.telemetry import Telemetry

    class Hook(Telemetry):
        def span(self, name, cat="span", **args):
            return _Span(window, name, args)

    return Hook(enabled=False)


class _Span:
    def __init__(self, run: "_Window", name: str, args: Dict):
        self.run, self.name, self.args = run, name, args
        self.ann = None

    def __enter__(self):
        if self.run.tracing:
            self.run.count_work(self.name, self.args)
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)
        if exc[0] is None:
            self.run.poll()
        return False


class _Window:
    def __init__(self, cell, engine, seed: int, seconds: float,
                 trace_dir: str = None, rate: float = None):
        from repro.serving.scheduler import Scheduler
        import jax
        mix = cell.mix
        self.cell, self.engine, self.seed = cell, engine, seed
        self.seconds = seconds
        self.drain_limit = mix["drain_limit_s"]
        self.arrivals = traffic.arrivals(mix, seconds, rate)
        self.vocab = cell.config["vocab_size"]
        self.timing = [traffic.RequestTiming(a.due_s) for a in self.arrivals]
        self.outputs: Dict[int, List[int]] = {}
        self.prompts: Dict[int, List[int]] = {}
        self.next = 0
        self.shapes = work.shapes_from_config(cell.config)
        self.sched = Scheduler(engine, mix["pool_rows"],
                               rng=jax.random.PRNGKey(seed % (1 << 31)),
                               telemetry=_telemetry(self))
        self.trace_dir = trace_dir
        half = min(mix["trace_seconds"], seconds) / 2
        self.trace_span = (seconds / 2 - half, seconds / 2 + half)
        self.tracing = False
        self.traced = False
        self.launches = {"prefill_forwards": 0, "first_tokens": 0}
        self.win_ann = None
        self.work = {"prefill_flops": 0, "prefill_kernel_roofline_s": 0.0,
                     "decode_roofline_s": 0.0, "decode_chunks": 0}

    def now(self) -> float:
        return time.perf_counter() - self.t0

    # -- arrivals and the traced window ----------------------------------

    def poll(self) -> None:
        from repro.serving.scheduler import Request
        t = self.now()
        while self.next < len(self.arrivals) \
                and self.arrivals[self.next].due_s <= t:
            a, rid = self.arrivals[self.next], self.next
            toks = traffic.prompt_tokens(self.seed, rid, a.prompt_len,
                                         self.vocab)
            self.prompts[rid] = toks
            self.sched.submit(Request(rid=rid, tokens=tuple(toks),
                                      max_new_tokens=a.out_len))
            self.next += 1
        if self.trace_dir is not None:
            self._trace_switch(t)
        if t > self.seconds + self.drain_limit:
            raise _DrainOver()

    def _trace_switch(self, t: float) -> None:
        import jax
        if not self.traced and not self.tracing and t >= self.trace_span[0]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.win_ann = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW)
            self.win_ann.__enter__()
            self.tracing = True
        elif self.tracing and t >= self.trace_span[1]:
            self.stop_trace()

    def stop_trace(self) -> None:
        import jax
        if self.tracing:
            self.win_ann.__exit__(None, None, None)
            # writing the trace stalls the loop for tens of seconds, and the
            # requests due meanwhile are then admitted together: the
            # scheduler's counts up to here are those of an untraced run
            self.launches = {
                "prefill_forwards": self.sched.stats.prefill_forwards,
                "first_tokens": sum(t.first is not None
                                    for t in self.timing)}
            jax.profiler.stop_trace()
            self.tracing, self.traced = False, True

    # -- required work of each span (traced window only) -----------------

    def count_work(self, name: str, args: Dict) -> None:
        from repro.serving.scheduler import DECODING, PREFILLING
        s, pk = self.shapes, harness.peaks(self.device_kind)
        slots = [x for x in self.sched.pool.slots if x is not None]
        c, P = s.block, self.engine.prefill_chunk
        if name == "prefill_chunk_forward":
            rows, ends = [], 0
            for x in slots:
                nfull = len(x.request.tokens) // c * c
                if x.state == PREFILLING and x.filled < nfull:
                    n = min(P, nfull - x.filled)
                    rows.append((x.filled, n))
                    ends += x.filled + n == len(x.request.tokens)
            _agree(name, args, len(rows), sum(n for _, n in rows))
            fl, by = work.prefill(s, rows, ends)
            kf, kb = work.chunk_prefill_kernel(s, rows)
            self.work["prefill_flops"] += fl
            self.work["prefill_kernel_roofline_s"] += work.roofline_s(
                kf, kb, pk)
        elif name == "prefill_remainder_forward":
            rem = args["tokens"] // args["rows"]
            rows = [(x.filled, rem) for x in slots
                    if x.state == PREFILLING
                    and len(x.request.tokens) - x.filled == rem]
            _agree(name, args, len(rows), rem * len(rows))
            fl, _ = work.prefill(s, rows, len(rows))
            self.work["prefill_flops"] += fl
        elif name == "decode_chunk":
            live = [(x.filled + len(x.emitted),
                     x.request.max_new_tokens - len(x.emitted))
                    for x in slots if x.state == DECODING]
            _agree(name, args, len(live), None)
            self.work["decode_chunks"] += 1
            for step in range(args["chunk"]):
                pos = [p + step for p, left in live if step < left]
                if pos:
                    fl, by = work.decode_step(s, pos)
                    self.work["decode_roofline_s"] += work.roofline_s(
                        fl, by, pk)

    # -- the loop ---------------------------------------------------------

    def on_token(self, rid: int, tok: int) -> None:
        self.timing[rid].token(self.now())

    def on_complete(self, rid: int, toks: List[int]) -> None:
        self.timing[rid].done = True
        self.outputs[rid] = list(toks)

    def run(self, device_kind: str) -> None:
        import jax
        self.device_kind = device_kind
        self.t0 = time.perf_counter()
        n = len(self.arrivals)
        try:
            while True:
                self.poll()
                if self.sched.waiting or self.sched.pool.occupancy:
                    self.sched.run(self.on_token, self.on_complete)
                    continue
                if self.next >= n:
                    break
                wait = self.arrivals[self.next].due_s - self.now()
                if wait > 0:
                    with jax.profiler.TraceAnnotation(WAIT):
                        time.sleep(wait)
        except _DrainOver:
            pass
        self.elapsed = self.now()
        self.stop_trace()


def _agree(span: str, args: Dict, rows: int, tokens) -> None:
    """The rows (and tokens) counted for a span, held against what the
    scheduler reports in the span's arguments."""
    want = (args["rows"], args["tokens"] if tokens is not None else None)
    if (rows, tokens) != want:
        raise WorkMismatch(
            f"{span}: the benchmark counted {rows} rows and {tokens} tokens, "
            f"the scheduler reports {want[0]} and {want[1]}; "
            "bench/cells/serve.py count_work no longer follows the "
            "scheduler's choice of rows")


def _parallel(cell):
    """The program's parallel context for a cell on several chips: one
    mesh over them, all on the `model` axis (the program's own debug mesh,
    as its chip smoke test builds it). None on one chip."""
    if cell.chips == 1:
        return None
    from repro.launch.mesh import make_local_mesh
    from repro.parallel.sharding import ParallelCtx
    return ParallelCtx(mesh=make_local_mesh(model_shards=cell.chips))


def _scope(ctx):
    return contextlib.nullcontext() if ctx is None else ctx.mesh


def _engine(cell, params, mc, ctx):
    import jax.numpy as jnp
    from repro.serving import ServingEngine
    mix = cell.mix
    return ServingEngine(params, mc, max_seq=mix["max_seq"], ctx=ctx,
                         cache_dtype=jnp.dtype(mc.dtype),
                         decode_chunk=mix["decode_chunk"],
                         prefill_chunk=mix["prefill_chunk"])


def _warm(cell, engine) -> None:
    """One request per remainder length through a throwaway scheduler:
    compiles the prefill chunk, each remainder program and the decode
    chunk, and every small program between them."""
    from repro.serving.scheduler import Request, Scheduler
    mix = cell.mix
    sched = Scheduler(engine, mix["pool_rows"])
    block = mix["prompt"]["block"]
    for i, rem in enumerate(mix["prompt"]["remainders"]):
        sched.submit(Request(rid=i, tokens=tuple(range(4, 4 + block + rem)),
                             max_new_tokens=2 * mix["decode_chunk"]))
    sched.run()
    if engine.ctx is not None:
        # on a mesh the logits come out sharded over the vocabulary and the
        # sampling key replicated over the chips after a decode chunk, so
        # each count of rows a prefill launch returns, and an admission
        # after a decode chunk, is a program of its own: admit 1, 2, ...
        # pool_rows requests at once, each wave after the last one's decode
        rid = len(mix["prompt"]["remainders"])
        for g in range(1, mix["pool_rows"] + 1):
            for _ in range(g):
                sched.submit(Request(rid=rid,
                                     tokens=tuple(range(4, 5 + block)),
                                     max_new_tokens=mix["decode_chunk"] + 1))
                rid += 1
            sched.run()
    del sched
    # the pool returns the first g rows of its padded logits: one small
    # slicing program per g, which a busy window reaches for every g
    import jax.numpy as jnp
    mc = engine.cfg
    z = jnp.zeros((mix["pool_rows"], mc.padded_vocab_size), mc.dtype)
    for g in range(1, mix["pool_rows"] + 1):
        z[:g].block_until_ready()
    gc.collect()


def sample_for_check(window: _Window, seed: int, k: int) -> List[int]:
    """k finished requests drawn from the seed, the longest always in."""
    done = sorted(window.outputs)
    if not done:
        return []
    size = lambda r: len(window.prompts[r]) + len(window.outputs[r])
    longest = max(done, key=size)
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def check(params, cfg: Dict, prompts, outputs, rids,
          prec=reference.FP32) -> Dict:
    """Widest gap below the reference's best over the served tokens of
    `rids`, and how many tokens were compared."""
    ref = reference.model(cfg)
    widest, n, gaps = 0.0, 0, []
    for rid in rids:
        p, out = prompts[rid], outputs[rid]
        if not out:
            continue
        seq = list(p) + list(out[:-1])
        pos = list(range(len(p) - 1, len(seq)))
        logits = ref.logits(params, cfg, seq, pos, prec)
        g = reference.served_gaps(logits, out)
        widest, n = max(widest, float(g.max())), n + len(out)
        gaps.extend(g.tolist())
    q = np.quantile(gaps, [0.5, 0.9, 0.99]).tolist() if gaps else []
    return {"gap_max": widest, "tokens": n, "gap_quantiles": q,
            "gap_mean": float(np.mean(gaps)) if gaps else 0.0,
            "off_argmax": float(np.mean(np.asarray(gaps) > 0)) if gaps
            else 0.0}


def judge(res: Dict, limits: Dict):
    """The numbers compared beside their limits, and whether all hold."""
    checks = harness.judged(res, limits)
    return checks, harness.checks_ok(checks) \
        and res["tokens"] >= limits["min_tokens"]


def _shapes_and_layout(mc, ctx):
    """The parameter tree's shapes, and its shardings under `ctx` (None
    on one chip)."""
    import jax
    from repro.models import model as M
    from repro.parallel.sharding import param_shardings
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), mc))
    return shapes, None if ctx is None else param_shardings(shapes, ctx)


def setup(cell, seed: int, ctx=None):
    """Weights, engine and warm programs: everything before the window."""
    mc = harness.model_config(cell.config)
    shapes, layout = _shapes_and_layout(mc, ctx)
    params = weights.make(shapes, seed, cell.config["vocab_size"], layout)
    engine = _engine(cell, params, mc, ctx)
    _warm(cell, engine)
    return params, engine


def _decode_runs_agree(red: Dict, chunks: int, chips: int) -> None:
    """Every chip that ran a program in the traced window ran the decode
    chunk's program once per decode chunk, and `chips` chips did."""
    ran = {dev: n.get(DECODE_MODULE, 0)
           for dev, n in red["module_n_by_device"].items()}
    if len(ran) != chips or any(n != chunks for n in ran.values()):
        raise WorkMismatch(
            f"{DECODE_MODULE} ran {ran} times on each device in the traced "
            f"window, over {chunks} decode chunks on {chips} chips")


def run(cell, seed: int, seconds: float, trace: bool, device: Dict,
        t_start: float) -> Dict:
    ctx = _parallel(cell)
    with _scope(ctx):
        return _run(cell, ctx, seed, seconds, trace, device, t_start)


def _run(cell, ctx, seed: int, seconds: float, trace: bool, device: Dict,
         t_start: float) -> Dict:
    compiles = harness.count_compiles()
    params, engine = setup(cell, seed, ctx)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(harness.WORK_DIR, f"trace-{cell.name}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = _Window(cell, engine, seed, seconds, trace_dir)
    compiled_in_setup = compiles()
    setup_s = time.perf_counter() - t_start
    win.run(device["kind"])
    compiled_in_window = compiles() - compiled_in_setup
    memory = harness.memory_peak_bytes(cell.chips)
    stats = win.sched.stats
    counters = {"prefill_forwards": stats.prefill_forwards,
                "prefill_tokens": stats.prefill_tokens,
                "chunks": stats.chunks, "sheds": stats.sheds}
    del win.sched, engine
    gc.collect()

    t_check = time.perf_counter()
    rids = sample_for_check(win, seed, cell.mix["check_requests"])
    res = check(params, cell.config, win.prompts, win.outputs, rids)
    check_s = time.perf_counter() - t_check

    attempted = len(win.arrivals)
    done = [t for t in win.timing if t.done]
    failed = attempted - len(done)
    checks, correct = judge(res, cell.limits)
    dev = dict(device, memory_peak_bytes=memory)
    notes = {"requests": attempted, "finished": len(done),
             "compiles_in_window": compiled_in_window,
             "window_s": round(win.elapsed, 3), "check_s": round(check_s, 3),
             "checked_requests": len(rids),
             "tokens_compared": res["tokens"],
             "gap_p50_p90_p99": res["gap_quantiles"],
             "gap_mean": res["gap_mean"], "off_argmax": res["off_argmax"],
             "counters": counters}
    if trace:
        red = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(trace_dir), SPANS + (WAIT,)))
        _decode_runs_agree(red, win.work["decode_chunks"], cell.chips)
        record = {"trace": red, "work": win.work,
                  "launches": win.launches,
                  "peak": harness.peaks(device["kind"])}
        metrics = harness.per_layer_values(cell, record)
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        ttft = traffic.percentile([t.ttft for t in win.timing], 90) * 1e3
        tpot = traffic.percentile([t.tpot for t in win.timing], 90) * 1e3
        metrics = {"ttft_p90_ms": {"value": ttft, "unit": "ms"},
                   "tpot_p90_ms": {"value": tpot, "unit": "ms"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        breakdown = None
        notes["ttft_p50_ms"] = traffic.percentile(
            [t.ttft for t in win.timing], 50) * 1e3
        notes["tpot_p50_ms"] = traffic.percentile(
            [t.tpot for t in win.timing], 50) * 1e3
    return dict(correct=correct, attempted=attempted, failed=failed,
                metrics=metrics, device=dev, checks=checks,
                breakdown=breakdown, notes=notes)


def _line(**kw) -> None:
    print(json.dumps(harness.plain(kw), allow_nan=False), flush=True)


def calibrate(cell, seeds, control_seeds, seconds, sweep, device) -> None:
    """Readings for bench.calibrate, one JSON line each, from one set-up:
    with `sweep`, a window at each rate and the backlog it leaves; then per
    seed, fresh weights, a window at the cell's rate and the program's
    widest gap; on the control seeds, the widest gap of the token that the
    fp8 reference puts first at the same positions. Every line holds its
    numbers to the cell's limits under `checks` and says `correct`."""
    ctx = _parallel(cell)
    with _scope(ctx):
        _calibrate(cell, ctx, seeds, control_seeds, seconds, sweep, device)


def _calibrate(cell, ctx, seeds, control_seeds, seconds, sweep,
               device) -> None:
    params, engine = setup(cell, seeds[0], ctx)
    shapes, layout = _shapes_and_layout(engine.cfg, ctx)
    for rate in sweep:
        win = _Window(cell, engine, seeds[0], seconds, None, rate)
        win.run(device["kind"])
        closed = [t for t in win.timing if t.done and t.last is not None
                  and t.last <= seconds]
        ttft = [t.ttft for t in win.timing]
        half = len(ttft) // 2
        _line(kind="sweep", rate=rate, requests=len(win.arrivals),
              finished_by_close=len(closed), drain_s=win.elapsed - seconds,
              ttft_p50_first_half=traffic.percentile(ttft[:half], 50),
              ttft_p50_second_half=traffic.percentile(ttft[half:], 50),
              ttft_p90=traffic.percentile(ttft, 90),
              tpot_p90=traffic.percentile([t.tpot for t in win.timing], 90),
              prefill_forwards=win.sched.stats.prefill_forwards,
              chunks=win.sched.stats.chunks)
        del win
        gc.collect()
    ref = reference.model(cell.config)
    for seed in seeds:
        engine.params = params = None
        gc.collect()
        params = weights.make(shapes, seed, cell.config["vocab_size"],
                              layout)
        engine.params = params
        win = _Window(cell, engine, seed, seconds)
        win.run(device["kind"])
        rids = sample_for_check(win, seed, cell.mix["check_requests"])
        prompts, outputs = win.prompts, win.outputs
        failed = len(win.arrivals) - len(outputs)
        del win
        gc.collect()
        t = time.perf_counter()
        res = check(params, cell.config, prompts, outputs, rids)
        checks, correct = judge(res, cell.limits)
        _line(kind="program", seed=seed, correct=correct, checks=checks,
              tokens=res["tokens"], failed=failed,
              gap_quantiles=res["gap_quantiles"],
              gap_mean=res["gap_mean"], off_argmax=res["off_argmax"],
              check_s=time.perf_counter() - t)
        if seed not in control_seeds:
            continue
        worst, gaps = 0.0, []
        for rid in rids:
            p, o = prompts[rid], outputs[rid]
            seq = list(p) + list(o[:-1])
            pos = list(range(len(p) - 1, len(seq)))
            hi = ref.logits(params, cell.config, seq, pos)
            low = ref.logits(params, cell.config, seq, pos, reference.FP8)
            g = reference.control_gaps(hi, low)
            worst = max(worst, float(np.max(g)))
            gaps.extend(g.tolist())
        res = {"gap_max": worst, "tokens": len(gaps)}
        checks, correct = judge(res, cell.limits)
        _line(kind="control", seed=seed, correct=correct, checks=checks,
              gap_quantiles=np.quantile(gaps, [0.5, 0.9, 0.99]).tolist(),
              gap_mean=float(np.mean(gaps)),
              off_argmax=float(np.mean(np.asarray(gaps) > 0)))
