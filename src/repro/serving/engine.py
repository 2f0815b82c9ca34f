"""Serving engine: slot-based continuous batching over device-resident decode.

Slot/scheduler model (the default `serve` path): the engine owns a fixed
pool of `max_batch` cache slots — one batch row of a single pool cache —
and a `Scheduler` (serving/scheduler.py) admits/evicts requests *between*
device-resident decode chunks:

* admission: with `prefill_chunk=0` (monolithic) a queued request is
  prefilled alone (B=1), its cache rows are `dynamic_update_slice`d into a
  free pool slot, and its per-row position counter
  (`cache["lengths"][slot]`) starts at the prompt length; with
  `prefill_chunk=P` (chunked) the slot is claimed at t=0 and the prompt
  streams into the pool cache P tokens per scheduler round — interleaved
  with decode chunks so a long prompt cannot stall the pool — with every
  co-prefilling request's next chunk batched into ONE padded (g, P)
  forward (`pool_prefill_chunk`): per-row offsets and valid-token counts
  are traced, so one compile serves every prompt length and progress mix;
* decode: the whole pool scans `decode_chunk` tokens on device
  (model.decode_scan — one host sync per chunk), idle slots riding along
  finished-masked;
* retirement: EOS or an exhausted per-request token budget frees the slot
  for the next admission round, streaming the finished tokens back through
  a completion callback.

Because every cache write, rope position, attention mask and block fold is
per-row (core/cache.py), a slot decodes identically whatever its
neighbours are doing — continuous scheduling is byte-identical to the
static bucketed baseline, kept as `serve_static`.

Prefill strategy (linformer_causal): monolithically, the full-block prefix
(⌊S/c⌋·c tokens) is prefilled in ONE parallel forward that also
materializes the compressed cache; the ≤c-1 remainder tokens run through
the decode path. Chunked admission splits the full-block prefix into
fixed P-token chunks (P a multiple of c, so chunk boundaries are
block-fold boundaries) computed by a prefill-at-offset forward
(model.prefill_chunk → kernels' blockwise-causal-prefix path) against the
slot-resident compressed cache; the remainder runs through the decode
path exactly as before, batched per remainder-length group. Standard
attention prefills the full prompt in one pass (monolithic) or in P-token
chunks at any offset (chunked).

Chunked decode contract: generation runs as jitted `lax.scan` chunks of
`decode_chunk` tokens (model.decode_scan) — sampling, EOS masking, and the
cache update all stay on device, and the host syncs ONCE per chunk instead
of once per token. The per-token Python loop is kept as
`generate_batch_per_token` — the measured baseline of
benchmarks/decode_throughput.py.

Cache ownership: the chunk scan DONATES its cache buffers. The batch-level
helpers (`decode_tokens`) consume the cache they are given; the scheduler
path instead routes every donation through the pool's single owner
(scheduler.SlotPool), which swaps in the returned buffers atomically — a
live scheduler can therefore never observe a donated (invalidated) cache.

The decode-time win of the paper's technique shows up here as cache size:
c + r·S/c slots instead of S (≈14× at 32k, ≈16× at 512k) — see
benchmarks/table3_efficiency.py.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import cache as cache_lib
from repro.data.pipeline import EOS
from repro.models import model as model_lib
from repro.parallel.sharding import ParallelCtx
from repro.telemetry import as_telemetry, plan_attribution

# Leaves of the PAGED pool cache that live in the shared page arena —
# indexed by physical page (L, Np, ...), not by pool row. Every per-row
# gather/scatter must treat them wholesale (the arena is one shared object;
# rows reach it only through their page-table indirection).
PAGED_ARENA_KEYS = ("page_k", "page_v", "page_k_s", "page_v_s")

# Hand-picked decode-scan chunk length — the fallback the tuning table's
# platform-wide "decode_chunk" scalar overrides (repro/tune/table.py).
# Chunk length changes tick granularity (scheduling interleave), never
# per-request token streams — the decode-chunk-invariance contract.
DEFAULT_DECODE_CHUNK = 32


def bucket_requests(prompts: Sequence[Sequence[int]], max_batch: int
                    ) -> List[List[int]]:
    """Group request indices into equal-length buckets of ≤ max_batch."""
    by_len: Dict[int, List[int]] = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(len(p), []).append(i)
    buckets = []
    for _, idxs in sorted(by_len.items()):
        for j in range(0, len(idxs), max_batch):
            buckets.append(idxs[j:j + max_batch])
    return buckets


def _per_request_max_new(max_new_tokens: Union[int, Sequence[int]],
                         n: int) -> List[int]:
    if isinstance(max_new_tokens, int):
        return [max_new_tokens] * n
    out = list(max_new_tokens)
    if len(out) != n:
        raise ValueError(f"max_new_tokens has {len(out)} entries "
                         f"for {n} prompts")
    return out


class ServingEngine:
    def __init__(
        self,
        params,
        cfg: ModelConfig,
        *,
        max_seq: int,
        ctx: Optional[ParallelCtx] = None,
        cache_dtype=jnp.bfloat16,
        temperature: float = 0.0,
        decode_chunk: Optional[int] = None,
        attention_backend: Optional[str] = None,
        prefill_chunk: int = 0,
        cache_format: str = "dense",
        arena_pages: Optional[int] = None,
        page_dtype: str = "int8",
        telemetry=None,
    ):
        if attention_backend is not None:
            cfg = cfg.with_attention_backend(attention_backend)
        # Resolve the attention execution plan once per engine: fails fast
        # on an unshardable mesh at construction, and owns the pool cache's
        # placement (per-shard slots for the decode kernel's two pinned
        # operands under tensor parallelism).
        from repro.parallel.plan import resolve_attention_plan
        self.plan = resolve_attention_plan(cfg.attention, ctx)
        self.params = params
        self.cfg = cfg
        self.max_seq = max_seq
        self.ctx = ctx
        self.cache_dtype = cache_dtype
        self.temperature = temperature
        if decode_chunk is None:
            from repro.tune import table as tuning
            decode_chunk = tuning.scalar("decode_chunk",
                                         DEFAULT_DECODE_CHUNK)
        self.decode_chunk = max(1, decode_chunk)
        # repro-lint: allow[RL002] constructor arg normalization — host int
        self.prefill_chunk = int(prefill_chunk)
        # Paged, quantized pool storage (cache_format="paged"): the pool's
        # per-row K/V lives as int8/fp8 pages in a shared arena behind a
        # per-row page table; `arena_pages` (None = capacity-equivalent to
        # the dense pool) is the oversubscription knob. Affects ONLY the
        # slot-pool path — one-shot generate/serve_static still run dense.
        if cache_format not in ("dense", "paged"):
            raise ValueError(f"unknown cache_format {cache_format!r} "
                             "(expected 'dense' or 'paged')")
        self.cache_format = cache_format
        self.arena_pages = arena_pages
        self.page_dtype = page_dtype
        if self.paged:
            if cfg.attention.kind != "linformer_causal":
                raise ValueError(
                    "cache_format='paged' requires the linformer_causal "
                    f"attention family, got {cfg.attention.kind!r} (the "
                    "page size IS the attention block fold)")
            # resolves the dtype now: fails fast on fp8 without jnp support
            _, self._page_qmax = cache_lib.resolve_page_dtype(page_dtype)
        self.telemetry = as_telemetry(telemetry)
        # shape-level compile-cache proxies: a novel decode-scan length or
        # prefill shape forces a jit specialization (see _note_compile)
        self._prefill_shapes: set = set()
        self._attributed: set = set()   # facades holding this plan's record
        self._record_plan_attribution(self.telemetry)

        self._decode = jax.jit(
            lambda p, b, c: model_lib.decode_step(p, cfg, b, c, ctx=ctx))
        self._prefill = jax.jit(
            lambda p, b: model_lib.forward(
                p, cfg, b, ctx=ctx, return_cache=True,
                cache_max_seq=max_seq, cache_dtype=cache_dtype),
        )
        self._chunk_fns: Dict[int, Callable] = {}
        self._write_slot = jax.jit(self._write_slot_impl,
                                   donate_argnums=(0,))
        # Snapshot/restore surface (preemption + fault recovery): gather
        # does NOT donate (the pool stays live — a snapshot is a copy),
        # scatter/scrub/corrupt donate like every other pool mutation.
        self._snapshot_rows = jax.jit(self._gather_rows)
        self._restore_rows = jax.jit(self._scatter_rows,
                                     donate_argnums=(0,))
        self._scrub_row = jax.jit(self._scrub_row_impl, donate_argnums=(0,))
        self._corrupt_row = jax.jit(self._corrupt_row_impl,
                                    static_argnums=(2,), donate_argnums=(0,))
        if self.paged:
            # Paged pool mutations: the arena leaves are page-indexed, so
            # the generic per-row gather/scatter/scrub/corrupt shapes are
            # wrong for them — each gets a dedicated, page-table-aware jit.
            self._write_slot_paged = jax.jit(self._write_slot_paged_impl,
                                             donate_argnums=(0,))
            self._snapshot_rows_paged = jax.jit(self._gather_rows_paged)
            self._restore_row_paged = jax.jit(self._restore_row_paged_impl,
                                              donate_argnums=(0,))
            self._scrub_row_paged = jax.jit(self._scrub_row_paged_impl,
                                            donate_argnums=(0,))
            self._corrupt_row_paged = jax.jit(
                self._corrupt_row_paged_impl, static_argnums=(3,),
                donate_argnums=(0,))
            self._scrub_pages = jax.jit(self._scrub_pages_impl,
                                        donate_argnums=(0,))
            self._set_table_row = jax.jit(self._set_table_row_impl,
                                          donate_argnums=(0,))
        if self.prefill_chunk:
            blk = self._block()
            if self.prefill_chunk < blk or self.prefill_chunk % blk != 0:
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} must be a positive "
                    f"multiple of the attention block size ({blk}) so chunk "
                    "boundaries land on block-fold boundaries")
            self._pool_prefill_chunk = jax.jit(
                self._pool_prefill_chunk_impl, donate_argnums=(1,))
            self._pool_prefill_remainder = jax.jit(
                self._pool_prefill_remainder_impl, donate_argnums=(1,))
            self._reset_row = jax.jit(self._reset_row_impl,
                                      donate_argnums=(0,))

    # -- internals ------------------------------------------------------

    def _block(self) -> int:
        a = self.cfg.attention
        if a.kind == "linformer_causal":
            return a.linformer.block_size
        return 1

    @property
    def paged(self) -> bool:
        return self.cache_format == "paged"

    def max_pages_per_row(self) -> int:
        """Page-table width: one page per block fold over the pool's token
        capacity (max_seq + the chunked-prefill slack)."""
        return (self.max_seq + self.prefill_chunk) // self._block()

    def resolved_arena_pages(self, max_batch: int) -> int:
        """Physical arena size for a `max_batch`-row pool: the explicit
        `arena_pages` knob, or one full table per row + TRASH (capacity-
        equivalent to the dense pool — no oversubscription)."""
        if self.arena_pages is not None:
            return self.arena_pages
        return max_batch * self.max_pages_per_row() + 1

    def _record_plan_attribution(self, tel) -> None:
        """Emit the resolved plan's cost-attribution record (backend,
        per-form FLOPs/comm-bytes estimates) into `tel` — once per facade,
        so a per-run `serve(telemetry=...)` override still gets it."""
        if not tel.enabled or tel in self._attributed:
            return
        self._attributed.add(tel)
        rec = plan_attribution(self.plan, self.cfg.attention,
                               max_seq=self.max_seq,
                               prefill_chunk=self.prefill_chunk or None)
        tel.record(rec.pop("kind"), **rec)

    def _note_compile(self, fn_name: str, hit: bool) -> None:
        """Count a shape-level jit compile-cache hit/miss (a proxy: jax's
        own cache is keyed the same way — per (function, abstract shapes) —
        so a novel shape here is a novel trace + compile there)."""
        if self.telemetry.enabled:
            self.telemetry.metrics.counter(
                "serving_compile_cache_hit_total" if hit
                else "serving_compile_cache_miss_total", fn=fn_name).inc()

    def _note_table_stats(self, tel=None) -> None:
        """Drain the tuning table's trace-time lookup counters into the
        metrics registry (rides the compile-cache proxies above): how many
        kernel-knob resolutions hit a committed TUNING.json entry vs fell
        back to the hand-picked defaults since the last drain."""
        tel = tel if tel is not None else self.telemetry
        if not tel.enabled:
            return
        from repro.tune import table as tuning
        stats = tuning.consume_stats()
        for key, name in (("hits", "tuning_table_hit_total"),
                          ("misses", "tuning_table_miss_total")):
            if stats[key]:
                tel.metrics.counter(name).inc(stats[key])

    def _sample(self, logits: jax.Array, rng) -> jax.Array:
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(rng, logits / self.temperature, axis=-1)

    def prefill(self, tokens: np.ndarray) -> Tuple[Dict, jax.Array]:
        """tokens: (B, S) prompt. Returns (cache at t=S, last-token logits)."""
        B, S = tokens.shape
        c = self._block()
        nfull = (S // c) * c
        shape = (B, nfull)
        self._note_compile("prefill", hit=shape in self._prefill_shapes)
        self._prefill_shapes.add(shape)
        if nfull == 0:
            cache = model_lib.init_cache(self.cfg, batch=B,
                                         max_seq=self.max_seq,
                                         dtype=self.cache_dtype)
            logits = None
        else:
            batch = {"tokens": jnp.asarray(tokens[:, :nfull])}
            logits_all, _, cache = self._prefill(self.params, batch)
            logits = logits_all[:, -1]
        for t in range(nfull, S):
            logits_t, cache = self._decode(
                self.params, {"tokens": jnp.asarray(tokens[:, t:t + 1])},
                cache)
            logits = logits_t[:, 0]
        return cache, logits

    def _chunk_fn(self, n: int) -> Callable:
        """Jitted n-step device-resident decode (cached per scan length)."""
        fn = self._chunk_fns.get(n)
        self._note_compile("decode_chunk", hit=fn is not None)
        if fn is None:
            cfg, ctx, temp = self.cfg, self.ctx, self.temperature
            fn = jax.jit(
                lambda p, cur, fin, cache, rng: model_lib.decode_scan(
                    p, cfg, cur, fin, cache, rng, n_steps=n, eos_id=EOS,
                    temperature=temp, ctx=ctx),
                donate_argnums=(3,))
            self._chunk_fns[n] = fn
        return fn

    # -- chunked-prefill internals ---------------------------------------

    @staticmethod
    def _gather_rows(pool: Dict, idx: jax.Array) -> Dict:
        """Stack pool rows `idx` into a B=len(idx) sub-cache. Cache leaves
        are (L, B, ...) except the per-row `lengths` (B,). Paged arena
        leaves ride through WHOLE: the gathered rows' page-table slices
        keep indexing the one shared arena."""
        return {k: (v if k in PAGED_ARENA_KEYS
                    else jnp.take(v, idx, axis=0 if k == "lengths" else 1))
                for k, v in pool.items()}

    @staticmethod
    def _scatter_rows(pool: Dict, sub: Dict, idx: jax.Array) -> Dict:
        """Write a sub-cache back into pool rows `idx` (inverse of
        `_gather_rows`). Duplicate indices are benign ONLY when they carry
        identical rows (the batch-padding trick below relies on this:
        `.set` scatter semantics make the duplicate a no-op rewrite; a
        duplicated paged row scatters identical bytes to the same pages).
        The sub-forward's arena leaves REPLACE the pool's — the sub held
        the whole arena, and untouched pages passed through unchanged."""
        out = {}
        for k, v in pool.items():
            upd = sub[k].astype(v.dtype)
            if k in PAGED_ARENA_KEYS:
                out[k] = upd
            else:
                out[k] = (v.at[idx].set(upd) if k == "lengths"
                          else v.at[:, idx].set(upd))
        return out

    def _pool_prefill_chunk_impl(self, params, pool: Dict, tokens: jax.Array,
                                 n_valid: jax.Array, idx: jax.Array):
        """Gather rows `idx`, run one prefill-at-offset chunk forward over
        them, scatter the advanced cache state back. Donates `pool`."""
        sub = self._gather_rows(pool, idx)
        logits, sub = model_lib.prefill_chunk(
            params, self.cfg, {"tokens": tokens}, sub, n_valid, ctx=self.ctx)
        return self._scatter_rows(pool, sub, idx), logits

    def _pool_prefill_remainder_impl(self, params, pool: Dict,
                                     tokens: jax.Array, idx: jax.Array):
        """Feed the sub-block remainder of a prompt (1 <= rem =
        tokens.shape[1] < block size) through the decode path against the
        gathered rows — exactly what the monolithic prefill does for its
        remainder, but batched over every request in the same remainder
        group. The steps run as one `lax.scan`, so a remainder length
        compiles one decode step, not rem."""
        def step(carry, tok):
            logits, cache = model_lib.decode_step(
                params, self.cfg, {"tokens": tok[:, None]}, carry[1],
                ctx=self.ctx)
            return (logits[:, 0], cache), None

        sub = self._gather_rows(pool, idx)
        first = jax.eval_shape(step, (None, sub), tokens[:, 0])[0][0]
        (logits, sub), _ = jax.lax.scan(
            step, (jnp.zeros(first.shape, first.dtype), sub), tokens.T)
        return self._scatter_rows(pool, sub, idx), logits

    @staticmethod
    def _scrub_row_impl(pool: Dict, row: jax.Array) -> Dict:
        """Zero pool row `row` — cache leaves AND its position counter.
        Quarantine needs a real scrub, not the lengths-only reset: a
        faulty row may hold NaN/Inf, and unlike finite stale garbage a NaN
        would LEAK through the next occupant's additive attention masks
        (NaN + (-1e9) is still NaN)."""
        out = {}
        for k, v in pool.items():
            if k == "lengths":
                out[k] = v.at[row].set(0)
            else:
                zero = jnp.zeros_like(
                    jax.lax.dynamic_slice_in_dim(v, row, 1, axis=1))
                out[k] = jax.lax.dynamic_update_slice_in_dim(
                    v, zero, row, axis=1)
        return out

    @staticmethod
    def _corrupt_row_impl(pool: Dict, row: jax.Array, mode: str) -> Dict:
        """Fault-injection primitive: corrupt row `row`'s cache leaves in
        place. mode='nan' poisons with NaN (exercises the NaN guard);
        mode='garble' applies a finite, deterministic bit-change (models a
        silent device fault — wrong bytes, nothing for the guard to see).
        `lengths` is untouched: the row keeps decoding, just wrongly."""
        out = {}
        for k, v in pool.items():
            if k == "lengths":
                out[k] = v
                continue
            rowv = jax.lax.dynamic_slice_in_dim(v, row, 1, axis=1)
            if mode == "nan":
                upd = jnp.full_like(rowv, jnp.nan)
            elif mode == "garble":
                upd = rowv * jnp.asarray(-1.5, v.dtype) \
                    + jnp.asarray(0.25, v.dtype)
            else:
                raise ValueError(f"unknown corruption mode {mode!r}")
            out[k] = jax.lax.dynamic_update_slice_in_dim(v, upd, row, axis=1)
        return out

    @staticmethod
    def _reset_row_impl(pool: Dict, row: jax.Array) -> Dict:
        """Zero a row's position counter for incremental (chunked) prefill.
        Only `lengths` needs resetting: stale K/V from the slot's previous
        occupant is never visible — every mask is bounded by the row's
        committed length, and both the chunk fold and the decode-time ring
        write land before visibility reaches them."""
        out = dict(pool)
        out["lengths"] = pool["lengths"].at[row].set(0)
        if "page_table" in pool:
            # defensive: a reset paged row must never fold through a stale
            # table entry into a page that has since changed hands
            out["page_table"] = pool["page_table"].at[:, row].set(-1)
        return out

    # -- paged-pool internals (cache_format="paged") ----------------------

    def _write_slot_paged_impl(self, pool: Dict, slot: Dict, row: jax.Array,
                               tab: jax.Array) -> Dict:
        """Monolithic admission into a paged pool: quantize the request's
        dense B=1 slot cache — raw ring per (token, head), compressed slots
        per (block, head) — and scatter the block pages through `tab`, the
        row's new page table (block-ordered page ids, -1 past the prompt's
        committed blocks; -1 entries redirect their write to TRASH)."""
        pdt = pool["page_k"].dtype
        trash = pool["page_k"].shape[1] - 1
        out = dict(pool)
        for src, dq, ds in (("raw_k", "raw_k_q", "raw_k_s"),
                            ("raw_v", "raw_v_q", "raw_v_s")):
            q, s = cache_lib.quantize_blockwise(
                slot[src], axes=(4,), dtype=pdt, qmax=self._page_qmax)
            out[dq] = pool[dq].at[:, row].set(q[:, 0])
            out[ds] = pool[ds].at[:, row].set(s[:, 0])
        L, Np, r, Hkv, Dh = pool["page_k"].shape
        maxp = pool["page_table"].shape[2]
        dst = jnp.where(tab >= 0, tab, trash)
        for src, dq, ds in (("comp_k", "page_k", "page_k_s"),
                            ("comp_v", "page_v", "page_v_s")):
            blocks = slot[src][:, 0].reshape(L, maxp, r, Hkv, Dh)
            q, s = cache_lib.quantize_blockwise(
                blocks, axes=(2, 4), dtype=pdt, qmax=self._page_qmax)
            out[dq] = pool[dq].at[:, dst].set(q)
            out[ds] = pool[ds].at[:, dst].set(s)
        out["page_table"] = pool["page_table"].at[:, row].set(tab)
        out["lengths"] = pool["lengths"].at[row].set(slot["lengths"][0])
        return out

    @staticmethod
    def _gather_rows_paged(pool: Dict, idx: jax.Array) -> Dict:
        """Snapshot gather for a paged pool: per-row ring + lengths, plus
        the payload and scale of EVERY table entry (unallocated entries
        clip to page 0; `snapshot_pool_rows` slices to the committed page
        count before the snapshot leaves the engine, so those garbage
        reads are never part of a snapshot's bytes)."""
        g = {k: jnp.take(v, idx, axis=0 if k == "lengths" else 1)
             for k, v in pool.items() if k not in PAGED_ARENA_KEYS}
        Np = pool["page_k"].shape[1]
        safe = jnp.clip(g.pop("page_table")[0], 0, Np - 1)     # (g, maxp)
        g["pages_k"] = pool["page_k"][:, safe]      # (L, g, maxp, r, Hkv, Dh)
        g["pages_v"] = pool["page_v"][:, safe]
        g["pages_k_s"] = pool["page_k_s"][:, safe]  # (L, g, maxp, Hkv)
        g["pages_v_s"] = pool["page_v_s"][:, safe]
        return g

    @staticmethod
    def _restore_row_paged_impl(pool: Dict, sub: Dict, row: jax.Array,
                                tab: jax.Array) -> Dict:
        """Scatter a paged snapshot back into `row`: ring + lengths by row,
        page payloads+scales into the FRESH pages of `tab` (maxp-padded
        with zero pages aimed at TRASH). Physical placement is free to
        differ from capture — rows only ever reach pages through the
        table, so the resumed math (and token stream) is byte-identical."""
        trash = pool["page_k"].shape[1] - 1
        dst = jnp.where(tab >= 0, tab, trash)
        out = dict(pool)
        for k in ("raw_k_q", "raw_v_q", "raw_k_s", "raw_v_s"):
            out[k] = pool[k].at[:, row].set(sub[k][:, 0].astype(pool[k].dtype))
        for sk, pk in (("pages_k", "page_k"), ("pages_v", "page_v"),
                       ("pages_k_s", "page_k_s"), ("pages_v_s", "page_v_s")):
            out[pk] = pool[pk].at[:, dst].set(sub[sk].astype(pool[pk].dtype))
        out["page_table"] = pool["page_table"].at[:, row].set(tab)
        out["lengths"] = pool["lengths"].at[row].set(sub["lengths"][0])
        return out

    @staticmethod
    def _scrub_row_paged_impl(pool: Dict, row: jax.Array) -> Dict:
        """Paged quarantine scrub: zero the row's RING leaves (its only
        per-row payload — NaN scales would leak through a later occupant's
        additive masks exactly like NaN K/V), reset its counter, and clear
        its table. The row's arena pages are zeroed separately, by the
        allocator's scrub-before-reuse callback when they are freed."""
        out = dict(pool)
        for k in ("raw_k_q", "raw_v_q", "raw_k_s", "raw_v_s"):
            out[k] = pool[k].at[:, row].set(jnp.zeros((), pool[k].dtype))
        out["page_table"] = pool["page_table"].at[:, row].set(-1)
        out["lengths"] = pool["lengths"].at[row].set(0)
        return out

    @staticmethod
    def _corrupt_row_paged_impl(pool: Dict, row: jax.Array, dst: jax.Array,
                                mode: str) -> Dict:
        """Paged fault injection: corrupt the row's ring AND the pages its
        table owns (`dst`: block-ordered page ids, TRASH-padded). Integer
        payloads take a deterministic XOR bit-flip — NaN is a float
        concept, so in 'nan' mode the poison enters through the fp32
        SCALES, which the dequant multiplies into every attended value;
        float leaves keep the dense path's NaN fill / affine garble.
        `lengths` and the table are untouched: the row keeps decoding,
        just wrongly."""
        if mode not in ("nan", "garble"):
            raise ValueError(f"unknown corruption mode {mode!r}")

        def bad(x):
            if jnp.issubdtype(x.dtype, jnp.integer):
                return x if mode == "nan" \
                    else x ^ jnp.asarray(0x55, x.dtype)
            if mode == "nan":
                return jnp.full_like(x, jnp.nan)
            return x * jnp.asarray(-1.5, x.dtype) + jnp.asarray(0.25, x.dtype)

        out = dict(pool)
        for k in ("raw_k_q", "raw_v_q", "raw_k_s", "raw_v_s"):
            out[k] = pool[k].at[:, row].set(bad(pool[k][:, row]))
        for k in PAGED_ARENA_KEYS:
            out[k] = pool[k].at[:, dst].set(bad(pool[k][:, dst]))
        return out

    @staticmethod
    def _scrub_pages_impl(pool: Dict, ids: jax.Array) -> Dict:
        """Zero arena pages `ids` — payload AND scales: a freed page must
        never leak one request's KV bytes (or NaN) into the next tenant's
        math or snapshot."""
        out = dict(pool)
        for k in PAGED_ARENA_KEYS:
            out[k] = pool[k].at[:, ids].set(jnp.zeros((), pool[k].dtype))
        return out

    @staticmethod
    def _set_table_row_impl(pool: Dict, row: jax.Array,
                            tab: jax.Array) -> Dict:
        out = dict(pool)
        out["page_table"] = pool["page_table"].at[:, row].set(tab)
        return out

    def _pad_page_ids(self, page_ids: Sequence[int]) -> np.ndarray:
        """A row's block-ordered page ids as a fixed (maxp,) table row,
        -1-padded — one compile for every count."""
        maxp = self.max_pages_per_row()
        if len(page_ids) > maxp:
            raise ValueError(f"{len(page_ids)} pages exceed the table "
                             f"width {maxp}")
        tab = np.full((maxp,), -1, np.int32)
        tab[:len(page_ids)] = page_ids
        return tab

    # -- paged slot-pool surface (consumed by serving/scheduler.py) --------

    def write_pool_slot_paged(self, pool: Dict, slot_cache: Dict, row: int,
                              page_ids: Sequence[int]) -> Dict:
        """Paged monolithic admission (donates `pool`): quantize the B=1
        dense slot cache into `row`'s ring + the freshly allocated
        `page_ids` (one per committed prompt block, in block order)."""
        pool = self._write_slot_paged(
            pool, slot_cache, jnp.asarray(row, jnp.int32),
            jnp.asarray(self._pad_page_ids(page_ids)))
        return self.plan.place_cache(pool)

    def restore_pool_rows_paged(self, pool: Dict, sub: Dict, row: int,
                                page_ids: Sequence[int]) -> Dict:
        """Paged inverse of `snapshot_pool_rows` (donates `pool`): the
        snapshot's pages land in the freshly allocated `page_ids` (len ==
        the snapshot's committed page count)."""
        npv = len(page_ids)
        maxp = self.max_pages_per_row()
        pads = {}
        for k, v in sub.items():
            if k.startswith("pages_"):
                # repro-lint: allow[RL002] host snapshot leaves
                v = np.asarray(v)
                if v.shape[1] != npv:
                    raise ValueError(
                        f"snapshot holds {v.shape[1]} pages in {k} but "
                        f"{npv} pages were allocated")
                pad = np.zeros((v.shape[0], maxp - npv) + v.shape[2:],
                               v.dtype)
                pads[k] = jnp.asarray(np.concatenate([v, pad], axis=1))
            else:
                pads[k] = jnp.asarray(v)
        pool = self._restore_row_paged(
            pool, pads, jnp.asarray(row, jnp.int32),
            jnp.asarray(self._pad_page_ids(page_ids)))
        return self.plan.place_cache(pool)

    def scrub_arena_pages(self, pool: Dict, page_ids: Sequence[int]) -> Dict:
        """Zero arena pages (donates `pool`) — the PageAllocator's
        scrub-before-reuse callback. Ids are TRASH-padded to the table
        width so every free shares one compile (zeroing TRASH is
        harmless)."""
        if len(page_ids) == 0:
            return pool
        trash = int(pool["page_k"].shape[1]) - 1
        maxp = self.max_pages_per_row()
        ids = list(page_ids) + [trash] * (maxp - len(page_ids))
        pool = self._scrub_pages(pool, jnp.asarray(ids, jnp.int32))
        return self.plan.place_cache(pool)

    def write_table_row(self, pool: Dict, row: int,
                        page_ids: Sequence[int]) -> Dict:
        """Publish `row`'s page list to the device table (donates `pool`) —
        the on-demand growth step: the allocator appends pages on the host,
        then the whole block-ordered list is rewritten here (-1 past the
        end, so unallocated folds keep redirecting to TRASH)."""
        pool = self._set_table_row(
            pool, jnp.asarray(row, jnp.int32),
            jnp.asarray(self._pad_page_ids(page_ids)))
        return self.plan.place_cache(pool)

    def clear_table_row(self, pool: Dict, row: int) -> Dict:
        """Retirement (donates `pool`): point every future fold of the now
        idle, finished-masked row at TRASH before its pages return to the
        free list — a stale table entry over a re-allocated page would let
        a dead row write into a live tenant's KV bytes."""
        return self.write_table_row(pool, row, ())

    def corrupt_pool_row_paged(self, pool: Dict, row: int,
                               page_ids: Sequence[int], mode: str) -> Dict:
        """Paged fault-injection entry point: corrupt `row`'s ring and its
        owned pages (donates `pool`). mode: 'nan' | 'garble'."""
        tab = self._pad_page_ids(page_ids)
        trash = int(pool["page_k"].shape[1]) - 1
        dst = np.where(tab >= 0, tab, trash).astype(np.int32)
        pool = self._corrupt_row_paged(pool, jnp.asarray(row, jnp.int32),
                                       jnp.asarray(dst), mode)
        return self.plan.place_cache(pool)

    # -- slot-pool surface (consumed by serving/scheduler.py) -------------

    def init_pool_cache(self, max_batch: int) -> Dict:
        """A fresh (max_batch)-row pool cache, every slot idle at t=0.

        Chunked prefill allocates `prefill_chunk` tokens of SLACK beyond
        max_seq: a padded final chunk writes its full P-token window at the
        row's offset, and without slack a window crossing max_seq would be
        CLAMPED by dynamic_update_slice — shifting the write down over
        earlier, still-valid slots. The slack region only ever holds padding
        junk (budget checks cap real content at max_seq).

        Under a mesh the pool is laid out per the plan's cache specs —
        KV-head axis sharded over tensor parallelism, so the decode
        kernel's two pinned operands hold per-shard slots — and every
        donating consumer (decode scans, slot writes, prefill chunks)
        inherits that layout."""
        slack = self.prefill_chunk  # 0 in monolithic mode
        if self.paged:
            a = self.cfg.attention
            cache = cache_lib.init_paged_cache(
                num_layers=self.cfg.num_layers, batch=max_batch,
                max_seq=self.max_seq + slack,
                block_size=a.linformer.block_size,
                block_slots=a.linformer.block_slots,
                num_kv_heads=a.num_kv_heads, head_dim=a.head_dim,
                arena_pages=self.resolved_arena_pages(max_batch),
                page_dtype=self.page_dtype)
            return self.plan.place_cache(cache)
        cache = model_lib.init_cache(self.cfg, batch=max_batch,
                                     max_seq=self.max_seq + slack,
                                     dtype=self.cache_dtype)
        return self.plan.place_cache(cache)

    @staticmethod
    def _write_slot_impl(pool: Dict, slot: Dict, row: jax.Array) -> Dict:
        """Copy a B=1 cache into pool row `row`. Cache leaves are
        (L, B, ...) except the per-row `lengths` (B,)."""
        out = {}
        for key, v in pool.items():
            axis = 0 if key == "lengths" else 1
            out[key] = jax.lax.dynamic_update_slice_in_dim(
                v, slot[key].astype(v.dtype), row, axis=axis)
        return out

    def write_pool_slot(self, pool: Dict, slot_cache: Dict, row: int) -> Dict:
        """Admission write: donate `pool`, return it with `row` replaced by
        the request's prefilled cache (traced row index — one compile)."""
        return self._write_slot(pool, slot_cache, jnp.asarray(row, jnp.int32))

    def pool_chunk_fn(self, n: int) -> Callable:
        """The scheduler's decode-chunk entry point (donates the cache —
        call through the pool owner only)."""
        return self._chunk_fn(n)

    def prefill_request(self, tokens: Sequence[int], rng: jax.Array
                        ) -> Tuple[Dict, int]:
        """Prefill ONE request (B=1). Returns (slot cache positioned at the
        prompt length, first sampled token)."""
        arr = np.asarray([list(tokens)], np.int32)
        cache, logits = self.prefill(arr)
        # repro-lint: allow[RL002] first-token sync (B=1 path)
        first = int(np.asarray(self._sample(logits, rng))[0])
        return cache, first

    def reset_pool_row(self, pool: Dict, row: int) -> Dict:
        """Mark pool row `row` empty at t=0 for incremental prefill
        (donates `pool`; route through the SlotPool owner)."""
        return self._reset_row(pool, jnp.asarray(row, jnp.int32))

    def snapshot_pool_rows(self, pool: Dict, rows: Sequence[int],
                           pad_to: int) -> List[Dict]:
        """Host-side copies of pool rows `rows` (does NOT donate — the pool
        stays live): one padded gather (`_gather_rows`, rows duplicated to
        `pad_to` so every capture of a pool shares one compile) + one
        device_get, sliced into per-row B=1 sub-caches. Thanks to the
        compressed prefix each row is O(c + M) bytes, not O(n) — the
        low-rank-state property that makes preemption snapshots cheap."""
        g = len(rows)
        rows_p, _ = self._pad_rows(rows, pad_to=pad_to)
        idx = jnp.asarray(rows_p, jnp.int32)
        if not self.paged:
            # repro-lint: allow[RL002] snapshot pool->host copy
            sub = jax.device_get(self._snapshot_rows(pool, idx))
            return [{k: (v[j:j + 1] if k == "lengths" else v[:, j:j + 1])
                     for k, v in sub.items()} for j in range(g)]
        # Paged: the checksum covers the quantized ring AND pages AND every
        # scale leaf — any corrupt byte, payload or scale, fails verify().
        # repro-lint: allow[RL002] snapshot pool->host copy
        sub = jax.device_get(self._snapshot_rows_paged(pool, idx))
        c = self._block()
        out = []
        for j in range(g):
            # repro-lint: allow[RL002] host snapshot read
            npv = int(sub["lengths"][j]) // c   # committed (folded) pages
            d = {}
            for k, v in sub.items():
                if k == "lengths":
                    d[k] = v[j:j + 1]
                elif k.startswith("pages_"):
                    d[k] = v[:, j, :npv]
                else:
                    d[k] = v[:, j:j + 1]
            out.append(d)
        return out

    def restore_pool_rows(self, pool: Dict, sub: Dict, row: int) -> Dict:
        """Scatter a snapshot's B=1 sub-cache back into pool row `row`
        (donates `pool`) — the byte-exact inverse of `snapshot_pool_rows`.
        The result is re-placed per the attention plan so a mesh-sharded
        pool keeps its layout across a restore exactly as it does across
        donation round-trips."""
        pool = self._restore_rows(pool, sub,
                                  jnp.asarray([row], jnp.int32))
        return self.plan.place_cache(pool)

    def scrub_pool_row(self, pool: Dict, row: int) -> Dict:
        """Zero a quarantined row — cache leaves and position counter
        (donates `pool`; route through the SlotPool owner). Re-placed per
        the plan: the row-wise update gives the compiler no reason to keep
        the KV-head sharding, so the layout is pinned back explicitly."""
        fn = self._scrub_row_paged if self.paged else self._scrub_row
        pool = fn(pool, jnp.asarray(row, jnp.int32))
        return self.plan.place_cache(pool)

    def corrupt_pool_row(self, pool: Dict, row: int, mode: str) -> Dict:
        """Fault-injection entry point (serving/faults.py): corrupt row
        `row` in place (donates `pool`; re-placed like `scrub_pool_row`).
        mode: 'nan' | 'garble'."""
        pool = self._corrupt_row(pool, jnp.asarray(row, jnp.int32), mode)
        return self.plan.place_cache(pool)

    @staticmethod
    def _pad_rows(rows: Sequence[int], *arrays: np.ndarray, pad_to: int):
        """Pad a row batch to exactly `pad_to` BY DUPLICATING the last row
        (and the matching rows of every per-row array) — `.set` scatter
        writes the identical state twice, so the duplicate is harmless.
        The scheduler passes its pool size, so ONE compile serves every
        admission round of a pool, whatever the occupancy."""
        g = len(rows)
        if g == 0:
            raise ValueError("empty prefill row batch")
        if pad_to < g:
            raise ValueError(f"pad_to={pad_to} smaller than batch {g}")
        rows = list(rows) + [rows[-1]] * (pad_to - g)
        padded = [np.concatenate([a] + [a[-1:]] * (pad_to - g), axis=0)
                  for a in arrays]
        return rows, padded

    def pool_prefill_chunk(self, pool: Dict, rows: Sequence[int],
                           tokens: np.ndarray, n_valid: np.ndarray,
                           pad_to: int) -> Tuple[Dict, jax.Array]:
        """Advance rows' prefill by one padded chunk forward (donates
        `pool`). tokens: (g, prefill_chunk) int32, padded at the end;
        n_valid: (g,) real token counts. Rows are padded to `pad_to` (the
        pool size) by duplication (`_pad_rows`). Returns (pool, last-valid
        logits (g, V))."""
        g = len(rows)
        rows, (tokens, n_valid) = self._pad_rows(rows, tokens, n_valid,
                                                 pad_to=pad_to)
        pool, logits = self._pool_prefill_chunk(
            self.params, pool, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), jnp.asarray(rows, jnp.int32))
        return pool, logits[:g]

    def pool_prefill_remainder(self, pool: Dict, rows: Sequence[int],
                               tokens: np.ndarray,
                               pad_to: int) -> Tuple[Dict, jax.Array]:
        """Feed rows' final sub-block remainder tokens ((g, rem), rem <
        block size) through batched decode steps (donates `pool`). Same
        row padding as `pool_prefill_chunk`. Returns (pool, final-token
        logits (g, V))."""
        g = len(rows)
        rows, (tokens,) = self._pad_rows(rows, tokens, pad_to=pad_to)
        pool, logits = self._pool_prefill_remainder(
            self.params, pool, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(rows, jnp.int32))
        return pool, logits[:g]

    # -- public API -------------------------------------------------------

    def generate_batch(self, tokens: np.ndarray, max_new_tokens: int,
                       rng: Optional[jax.Array] = None) -> np.ndarray:
        """Greedy/temperature generation for one equal-length batch.
        tokens: (B, S) int array. Returns (B, max_new_tokens).

        Decodes in device-resident `decode_chunk`-token scans: one host sync
        per chunk (fetch tokens + all-finished early exit) instead of one per
        generated token."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        cache, logits = self.prefill(tokens)
        return self.decode_tokens(cache, logits, max_new_tokens, rng)

    def decode_tokens(self, cache: Dict, logits: jax.Array,
                      max_new_tokens: int,
                      rng: Optional[jax.Array] = None) -> np.ndarray:
        """Decode phase given a prefilled cache and last-token logits.
        NOTE: the chunk scan donates `cache` — it is consumed. Long-lived
        callers that must survive donation (the scheduler) own their cache
        through scheduler.SlotPool instead of calling this."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        B = logits.shape[0]
        outs = np.full((B, max_new_tokens), EOS, np.int32)
        finished = jnp.zeros((B,), bool)
        cur = self._sample(logits, rng)
        done = 0
        while done < max_new_tokens:
            n = min(self.decode_chunk, max_new_tokens - done)
            toks, cur, finished, _bad, cache, rng = self._chunk_fn(n)(
                self.params, cur, finished, cache, rng)
            # repro-lint: allow[RL002] the chunk's one sync
            outs[:, done:done + n] = np.asarray(toks)
            done += n
            # repro-lint: allow[RL002] rides the chunk's single sync boundary
            if bool(np.asarray(finished).all()):
                break
        return outs

    def generate_batch_per_token(self, tokens: np.ndarray,
                                 max_new_tokens: int,
                                 rng: Optional[jax.Array] = None
                                 ) -> np.ndarray:
        """Legacy per-token decode loop (one host round-trip per token) —
        kept as the measured baseline for benchmarks/decode_throughput.py."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        cache, logits = self.prefill(tokens)
        return self.decode_tokens_per_token(cache, logits, max_new_tokens,
                                            rng)

    def decode_tokens_per_token(self, cache: Dict, logits: jax.Array,
                                max_new_tokens: int,
                                rng: Optional[jax.Array] = None
                                ) -> np.ndarray:
        """Per-token decode phase (baseline counterpart of decode_tokens)."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        B = logits.shape[0]
        outs = np.zeros((B, max_new_tokens), np.int32)
        finished = jnp.zeros((B,), bool)
        cur = self._sample(logits, rng)
        for i in range(max_new_tokens):
            cur = jnp.where(finished, EOS, cur)
            # repro-lint: allow[RL002] per-token baseline loop
            outs[:, i] = np.asarray(cur)
            finished = finished | (cur == EOS)
            # repro-lint: allow[RL002] per-token baseline loop
            if bool(finished.all()):
                outs[:, i + 1:] = EOS
                break
            rng, sub = jax.random.split(rng)
            logits_t, cache = self._decode(
                self.params, {"tokens": cur[:, None].astype(jnp.int32)}, cache)
            cur = self._sample(logits_t[:, 0], sub)
        return outs

    @property
    def supports_continuous_batching(self) -> bool:
        """Slot scheduling needs per-row position counters, which only the
        transformer-family caches carry; ssm/hybrid caches share a scalar
        position (and recurrent state writes are not yet per-row)."""
        return self.cfg.family in model_lib._TRANSFORMER_FAMILIES

    def _check_budgets(self, prompts, budgets) -> None:
        for i, p in enumerate(prompts):
            if len(p) == 0:
                # fail fast: there are no logits to sample a first token
                # from (and a zero-token PREFILLING slot would never
                # activate, deadlocking the chunked scheduler)
                raise ValueError(f"request {i}: empty prompt")
            if budgets[i] <= 0:
                raise ValueError(f"request {i}: max_new_tokens="
                                 f"{budgets[i]} must be positive")
            if len(p) + budgets[i] > self.max_seq:
                raise ValueError(
                    f"request {i}: prompt {len(p)} + budget {budgets[i]} "
                    f"exceeds max_seq={self.max_seq}")

    def serve(self, prompts: Sequence[Sequence[int]],
              max_new_tokens: Union[int, Sequence[int]],
              max_batch: int = 8,
              *,
              arrival_chunks: Optional[Sequence[int]] = None,
              priorities: Optional[Sequence[int]] = None,
              deadlines: Optional[Sequence[Optional[int]]] = None,
              max_queue: Optional[int] = None,
              max_retries: int = 2,
              snapshot_chunks: int = 0,
              nan_guard: bool = True,
              fault_injector=None,
              on_token: Optional[Callable[[int, int], None]] = None,
              on_complete: Optional[Callable[[int, List[int]], None]] = None,
              rng: Optional[jax.Array] = None,
              return_scheduler: bool = False,
              telemetry=None):
        """Serve arbitrary mixed-length requests with slot-based continuous
        batching: a `max_batch`-slot pool, admission/retirement between
        decode chunks (serving/scheduler.py).

        `max_new_tokens` may be one int or a per-request sequence;
        `arrival_chunks` optionally replays an arrival trace (request i
        admissible after that much virtual time, in chunk units).

        SLO knobs (all default to the plain FCFS behavior): `priorities`
        (per-request class, lower = more urgent — a strictly more urgent
        arrival preempts the least-urgent running slot), `deadlines`
        (per-request absolute deadline in ticks, None = none), `max_queue`
        (bounded admission queue — overflow sheds the least-valued entry),
        `max_retries` + `snapshot_chunks` (fault recovery: retry budget and
        last-good-snapshot refresh period), `nan_guard` (quarantine rows
        whose logits go non-finite), `fault_injector` (serving/faults.py).
        A shed request's output is a `ShedResult` instead of a token list.

        `telemetry` overrides the engine's `Telemetry` facade for this run
        (span trace, per-request timelines, per-priority SLO histograms —
        docs/observability.md); None uses the engine's own, which defaults
        to the disabled no-op singleton.

        `on_token`/`on_complete` stream per-request progress. Returns
        outputs ordered like `prompts` (or (outputs, scheduler) with
        return_scheduler=True, for stats).

        Model families whose cache has no per-row position counters
        (ssm/hybrid) fall back to the static bucketed scheduler; streaming
        callbacks then fire after each bucket completes."""
        budgets = _per_request_max_new(max_new_tokens, len(prompts))
        slo = (priorities is not None or deadlines is not None
               or max_queue is not None or fault_injector is not None
               or snapshot_chunks)
        if not self.supports_continuous_batching:
            if return_scheduler or arrival_chunks is not None or slo:
                raise ValueError(
                    f"family {self.cfg.family!r} has a shared-scalar cache: "
                    "no continuous scheduler (serve falls back to the "
                    "static bucketed path, which has no scheduler stats, "
                    "no SLO/fault handling, and cannot replay an arrival "
                    "trace)")
            outputs = self.serve_static(prompts, budgets,
                                        max_batch=max_batch)
            for i, out in enumerate(outputs):
                if on_token is not None:
                    for tok in out:
                        on_token(i, tok)
                if on_complete is not None:
                    on_complete(i, out)
            return outputs
        from repro.serving.scheduler import Request, Scheduler
        n = len(prompts)
        arrivals = list(arrival_chunks) if arrival_chunks is not None \
            else [0] * n
        prios = list(priorities) if priorities is not None else [0] * n
        dls = list(deadlines) if deadlines is not None else [None] * n
        for name, seq in (("arrival_chunks", arrivals),
                          ("priorities", prios), ("deadlines", dls)):
            if len(seq) != n:
                raise ValueError(f"{name} has {len(seq)} entries "
                                 f"for {n} prompts")
        self._check_budgets(prompts, budgets)
        tel = telemetry if telemetry is not None else self.telemetry
        self._record_plan_attribution(tel)
        sched = Scheduler(self, max_batch, rng=rng, max_queue=max_queue,
                          max_retries=max_retries,
                          snapshot_chunks=snapshot_chunks,
                          nan_guard=nan_guard,
                          fault_injector=fault_injector,
                          telemetry=tel)
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=i, tokens=tuple(p),
                                 max_new_tokens=budgets[i],
                                 arrival_chunk=arrivals[i],
                                 priority=prios[i],
                                 deadline_ticks=dls[i]))
        with tel.span("serve", cat="engine", n_requests=n,
                      max_batch=max_batch):
            results = sched.run(on_token=on_token, on_complete=on_complete)
        self._note_table_stats(tel)
        outputs = [results[i] for i in range(n)]
        if return_scheduler:
            return outputs, sched
        return outputs

    def serve_static(self, prompts: Sequence[Sequence[int]],
                     max_new_tokens: Union[int, Sequence[int]],
                     max_batch: int = 8) -> List[List[int]]:
        """Static bucketed baseline: bucket by equal prompt length, decode
        each bucket to its LONGEST request budget (short requests pad out
        long ones — the waste continuous batching removes)."""
        budgets = _per_request_max_new(max_new_tokens, len(prompts))
        self._check_budgets(prompts, budgets)
        results: List[Optional[List[int]]] = [None] * len(prompts)
        for bucket in bucket_requests(prompts, max_batch):
            toks = np.asarray([list(prompts[i]) for i in bucket], np.int32)
            n = max(budgets[i] for i in bucket)
            gen = self.generate_batch(toks, n)
            for row, i in enumerate(bucket):
                out = gen[row, :budgets[i]].tolist()
                if EOS in out:
                    out = out[:out.index(EOS)]
                results[i] = out
        return results  # type: ignore

    def cache_bytes(self, batch: int) -> int:
        """Decode-cache footprint (the paper's memory claim, measurable).
        In paged mode this is the quantized pool: ring + scales + page
        arena (`arena_pages`, or the capacity-equivalent default) + table —
        the denominator of the capacity benchmark's equal-bytes pools."""
        if self.paged:
            a = self.cfg.attention
            spec = cache_lib.paged_cache_spec(
                num_layers=self.cfg.num_layers, batch=batch,
                max_seq=self.max_seq,
                block_size=a.linformer.block_size,
                block_slots=a.linformer.block_slots,
                num_kv_heads=a.num_kv_heads, head_dim=a.head_dim,
                arena_pages=self.arena_pages, page_dtype=self.page_dtype)
            return sum(int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
                       for v in spec.values())
        cache = model_lib.init_cache(self.cfg, batch=batch,
                                     max_seq=self.max_seq,
                                     dtype=self.cache_dtype)
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
