"""SLO-aware slot-based continuous-batching scheduler over the
device-resident decode loop.

Admission/eviction contract
---------------------------

The unit of work is a *slot*: one row of a fixed (max_batch)-row pool cache.
The scheduler mutates the pool ONLY between decode chunks:

* **Admission** — earliest-deadline-first within priority classes: arrived
  requests are ordered by (priority, deadline, submission order) — lower
  `priority` numbers are more urgent, `deadline_ticks=None` sorts last
  within its class, and with the default priority/deadline on every request
  the order degenerates to exactly the old FCFS. A queued request whose
  arrival time has passed claims a free slot. With `engine.prefill_chunk ==
  0` (monolithic) it is prefilled alone (B=1, its own forward) and its
  cache rows are `dynamic_update_slice`d into the pool; with
  `engine.prefill_chunk > 0` (chunked) the slot is claimed PREFILLING at
  t=0 and the prompt streams into the pool cache one fixed-size chunk per
  round, every co-prefilling row sharing ONE padded, batched forward.
  Admission never perturbs live rows: every cache write, rope position,
  attention mask and block fold is per-row (core/cache.py).
* **Preemption** — when no slot is free, an arrived request whose priority
  is STRICTLY more urgent than the least-urgent occupied slot evicts that
  slot: the victim's state is captured as a host-side `SlotSnapshot`
  (cache rows via the engine's `_gather_rows` — O(c + M) bytes per row,
  the compressed prefix making preemption cheap — plus `cur`, `finished`,
  emitted tokens and prefill progress) and the victim is requeued; when it
  is re-admitted the snapshot is `_scatter_rows`'d back and decode resumes
  byte-identically to an uninterrupted run. Strict inequality means a
  victim can never preempt its preemptor — no thrash.
* **Overload shedding** — `max_queue` bounds the admission queue: a submit
  beyond the bound sheds the entry that EDF would schedule LAST (lowest
  priority class, latest deadline, latest submission) with an explicit
  `ShedResult` instead of queueing unboundedly. Per round, a waiting
  request whose deadline can no longer be met even by the optimistic
  lower-bound estimate (`_needed_ticks`) is shed as infeasible rather than
  admitted to miss.
* **Decode** — the pool decodes `decode_chunk` tokens as one jitted
  `lax.scan` (model.decode_scan): ONE host sync per chunk, which now also
  carries a per-row non-finite-logits flag (the NaN/Inf guard — detection
  costs nothing extra).
* **Faults & quarantine** — a row flagged bad (NaN/Inf logits) or reported
  failed by an attached `FaultInjector` is quarantined at the chunk
  boundary: its tokens from the poisoned chunk are discarded, its row is
  scrubbed (zeroed — a NaN cache must never be left where additive masks
  could leak it to a later occupant), and the request is requeued from its
  last good snapshot (or from scratch when none exists — greedy decode
  makes that byte-identical too). Retries are bounded by `max_retries`;
  exhaustion sheds the request with an explicit ShedResult. A corrupt
  snapshot (checksum mismatch) is detected at restore and falls back to
  from-scratch. Neighbour rows' bytes are never touched — per-row masks
  make every row's math independent, so a fault-free co-resident request
  is byte-identical to a fault-free run (tests/test_serving_faults.py).
* **Eviction / retirement** — after the chunk's host sync, an EOS or an
  exhausted per-request `max_new_tokens` budget retires the slot; a
  completion past the request's deadline counts a `deadline_miss`.

The pool cache has a single owner (`SlotPool`): every donating mutation
(chunk scans, slot writes, restores, scrubs, fault corruption) routes
through it and swaps in the returned cache, so no other live reference can
dangle. Snapshot capture gathers WITHOUT donating.

Determinism: greedy decode of a request depends only on its own prompt —
per-row masks make every row's attention independent of its neighbours — so
continuous scheduling (with any mix of preemptions, requeues and restores)
produces byte-identical outputs to the static bucketed baseline
(`ServingEngine.serve_static`), under any arrival order and any pool size
(tests/test_serving_scheduler.py, tests/test_serving_faults.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import EOS
from repro.serving.paged import PageAllocator, pages_needed
from repro.serving.snapshot import SlotSnapshot, capture
from repro.telemetry import MetricsRegistry, as_telemetry

_INF = float("inf")

# ShedResult reasons
SHED_QUEUE_FULL = "queue_full"
SHED_DEADLINE_INFEASIBLE = "deadline_infeasible"
SHED_RETRIES_EXHAUSTED = "retries_exhausted"
SHED_PAGES_EXHAUSTED = "pages_exhausted"   # paged pool: the request's
#                        lifetime page need exceeds the whole arena — it
#                        could never run to completion, so it is refused
#                        up front rather than wedged mid-decode


@dataclasses.dataclass
class Request:
    """One generation request.

    `arrival_chunk`: the request becomes admissible once that much virtual
    time has passed (executed decode chunks + idle ticks, `stats.ticks`) —
    the replay knob for arrival traces (benchmarks/serving_throughput.py);
    0 = available immediately.

    `priority`: admission class — LOWER is more urgent (0 = interactive).
    Within a class, earliest `deadline_ticks` first, then submission order.
    A strictly more urgent arrival may preempt a less urgent running slot.

    `deadline_ticks`: absolute virtual-time deadline (None = no deadline).
    Used for EDF ordering, feasibility shedding, and the deadline_misses
    counter; it is an SLO signal, not a hard kill — a running request past
    its deadline finishes and counts a miss.

    Construction fails fast on malformed fields with the rid in the message
    (a bad request must never surface as an opaque shape error mid-decode).
    """

    rid: int
    tokens: Tuple[int, ...]
    max_new_tokens: int
    arrival_chunk: int = 0
    priority: int = 0
    deadline_ticks: Optional[int] = None

    def __post_init__(self):
        if len(self.tokens) == 0:
            raise ValueError(f"request {self.rid}: empty prompt (there are "
                             "no logits to sample a first token from)")
        if self.max_new_tokens <= 0:
            raise ValueError(f"request {self.rid}: max_new_tokens="
                             f"{self.max_new_tokens} must be positive")
        if self.arrival_chunk < 0:
            raise ValueError(f"request {self.rid}: arrival_chunk="
                             f"{self.arrival_chunk} must be >= 0")
        if self.deadline_ticks is not None and self.deadline_ticks < 0:
            raise ValueError(f"request {self.rid}: deadline_ticks="
                             f"{self.deadline_ticks} must be >= 0")


@dataclasses.dataclass(frozen=True)
class ShedResult:
    """Explicit rejection: the scheduler refused (or gave up on) a request
    instead of queueing it forever or streaming garbage. Returned in place
    of the token list."""

    rid: int
    reason: str        # SHED_QUEUE_FULL | SHED_DEADLINE_INFEASIBLE |
    #                    SHED_RETRIES_EXHAUSTED
    tick: int          # virtual time of the decision
    priority: int


# Slot states. A monolithically-admitted slot is born DECODING; under
# chunked admission (engine.prefill_chunk > 0) a slot is born PREFILLING —
# its prompt enters the pool cache one fixed-size chunk per scheduler round,
# interleaved with everyone else's decode chunks — and flips to DECODING
# when its first token is sampled. PREFILLING survives across rounds: the
# partial-prefill state is the row's cache contents + `_Slot.filled`.
PREFILLING = "prefilling"
DECODING = "decoding"


@dataclasses.dataclass
class _Slot:
    request: Request
    emitted: List[int]
    state: str = DECODING
    filled: int = 0                    # prompt tokens committed to the cache
    seq: int = 0                       # submission order (EDF tie-break)
    retries: int = 0                   # fault requeues consumed so far


@dataclasses.dataclass
class _QueueEntry:
    """A waiting request, possibly carrying resume state from a preemption
    or a fault requeue."""

    request: Request
    seq: int
    snapshot: Optional[SlotSnapshot] = None
    retries: int = 0

    def sort_key(self) -> Tuple[int, float, int]:
        """EDF within priority classes; submission order breaks ties. The
        max of this key over a set is also the shedding/preemption victim
        (the entry the schedule values least)."""
        dl = self.request.deadline_ticks
        return (self.request.priority, _INF if dl is None else dl, self.seq)


def _slot_sort_key(slot: _Slot) -> Tuple[int, float, int]:
    dl = slot.request.deadline_ticks
    return (slot.request.priority, _INF if dl is None else dl, slot.seq)


# ScheduleStats attribute -> metric name in the backing registry. The
# attribute surface (stats.chunks, stats.sheds += 1, ...) is unchanged from
# the pre-telemetry dataclass; the storage moved into a MetricsRegistry so
# one increment is visible to both the scheduler and the metrics export.
_STAT_COUNTERS = {
    "chunks": "serving_chunks_total",              # decode chunks executed
    "idle_ticks": "serving_idle_ticks_total",      # no-decode ticks (pool
    #                                                empty or all prefilling)
    "row_steps": "serving_row_steps_total",        # DECODING-slot steps
    "occupancy_sum": "serving_occupancy_sum",      # Σ per-chunk occupied frac
    #                                                (DECODING + PREFILLING)
    "prefill_forwards": "serving_prefill_forwards_total",  # prefill launches
    "prefill_tokens": "serving_prefill_tokens_total",  # real prompt tokens
    "preemptions": "serving_preemptions_total",    # snapshot + requeue evicts
    "sheds": "serving_sheds_total",                # explicit ShedResults
    "deadline_misses": "serving_deadline_misses_total",  # late completions
    "retries": "serving_retries_total",            # fault requeues
    "quarantines": "serving_quarantines_total",    # faulty rows isolated
    "snapshots": "serving_snapshots_total",        # snapshots captured
    "snapshot_corruptions": "serving_snapshot_corruptions_total",
    "page_preemptions": "serving_page_preemptions_total",  # evictions forced
    #                                                by arena-page pressure
}


class ScheduleStats:
    """Scheduler counters, stored in a `telemetry.MetricsRegistry`.

    A *view*: `stats.chunks` reads — and `stats.chunks += 1` writes — the
    `serving_chunks_total` counter of `stats.registry` (see
    `_STAT_COUNTERS` for the full name map), so the same numbers flow into
    the Prometheus/JSONL exports without a second set of hand-rolled ints.
    Each Scheduler owns a FRESH registry (plus the per-priority SLO
    histograms folded in at the end of `run`); a shared `Telemetry` facade
    adopts it per run, so warm reruns never accumulate across schedulers.
    All attributes except `occupancy_sum` read back as ints, exactly like
    the old dataclass fields."""

    __slots__ = ("registry", "_c")

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        object.__setattr__(self, "registry",
                           registry if registry is not None
                           else MetricsRegistry())
        object.__setattr__(self, "_c", {
            attr: self.registry.counter(name)
            for attr, name in _STAT_COUNTERS.items()})

    def __getattr__(self, name):
        try:
            c = object.__getattribute__(self, "_c")[name]
        except KeyError:
            raise AttributeError(name) from None
        return c.value if name == "occupancy_sum" else int(c.value)

    def __setattr__(self, name, value):
        c = self._c.get(name)
        if c is None:
            raise AttributeError(f"ScheduleStats has no counter {name!r}")
        # repro-lint: allow[RL002] metrics mirror ingests host floats
        c.value = float(value)

    @property
    def ticks(self) -> int:
        """Virtual time: executed chunks + idle ticks (arrival clock)."""
        return self.chunks + self.idle_ticks

    @property
    def mean_occupancy(self) -> float:
        """Mean occupied fraction over EXECUTED chunks (idle ticks, where
        nothing decoded, are excluded)."""
        return self.occupancy_sum / max(self.chunks, 1)

    def counters_line(self) -> str:
        """One-line SLO counter summary (surfaced by launch/serve.py)."""
        return (f"preemptions={self.preemptions} sheds={self.sheds} "
                f"deadline_misses={self.deadline_misses} "
                f"retries={self.retries} quarantines={self.quarantines} "
                f"snapshot_corruptions={self.snapshot_corruptions} "
                f"page_preemptions={self.page_preemptions}")


class SlotPool:
    """Sole owner of the live pool cache + per-slot decode state.

    All jitted mutations (slot writes, chunk scans, restores, scrubs,
    injected corruption) donate the cache and the pool swaps in the result,
    so external references can never observe a donated buffer. Snapshot
    capture (`snapshot_rows`) gathers without donating. Under a mesh the
    cache arrives from `engine.init_pool_cache` already laid out per the
    engine's AttentionPlan (KV-head axis sharded over tensor parallelism —
    per-shard slots for the decode kernel's pinned operands); donation and
    snapshot/restore round-trips preserve that layout, so the pool stays
    sharded for its whole life without the scheduler knowing a mesh exists.
    """

    def __init__(self, engine, max_batch: int):
        self.engine = engine
        self.max_batch = max_batch
        self.cache = engine.init_pool_cache(max_batch)
        if "lengths" not in self.cache:
            raise ValueError(
                "continuous batching needs per-row position counters "
                "(cache['lengths']); this model family has a shared scalar "
                "cache — use serve_static")
        self.cur = np.full((max_batch,), EOS, np.int32)
        self.finished = np.ones((max_batch,), bool)
        self.slots: List[Optional[_Slot]] = [None] * max_batch
        # Scheduler-round fast path (the warm-wall gap is round-dominated,
        # not scatter-dominated — docs/serving.md): the chunk scan's cur/
        # finished OUTPUTS are kept device-resident and fed straight back
        # into the next chunk, skipping two host->device uploads per round.
        # Any host-side row mutation (admit/activate/retire/restore/
        # begin_prefill) marks them dirty, and the next chunk re-uploads
        # the authoritative host mirrors — so the math is byte-identical
        # to re-uploading every round.
        self._cur_dev: Optional[jax.Array] = None
        self._fin_dev: Optional[jax.Array] = None
        self._rows_dirty = True
        # resolved jitted chunk callables, cached per scan length: avoids
        # re-resolving (and re-counting) through the engine every round
        self._chunk_fns: Dict[int, Callable] = {}
        # Paged pool (engine.cache_format == "paged"): the pool owns the
        # page allocator alongside the cache — every page the device table
        # references was handed out here, and every freed page is zeroed
        # (the scrub callback) before it can be reused.
        self.paged: bool = bool(getattr(engine, "paged", False))
        self.alloc: Optional[PageAllocator] = None
        self.pages_allocated = 0           # cumulative, for telemetry
        self.pages_freed = 0
        self.quant_error_bound = 0.0       # Σ 0.5·scale over snapshotted
        #                                    pages (worst-case abs error of
        #                                    symmetric int8 rounding)
        if self.paged:
            self.alloc = PageAllocator(
                engine.resolved_arena_pages(max_batch),
                scrub=self._scrub_freed_pages)

    def _scrub_freed_pages(self, pages) -> None:
        """PageAllocator scrub callback: zero the freed pages' device bytes
        BEFORE they return to the free list."""
        self.cache = self.engine.scrub_arena_pages(self.cache, pages)
        self.pages_freed += len(pages)

    def _alloc_pages(self, row: int, n: int) -> Optional[List[int]]:
        pages = self.alloc.alloc(row, n)
        if pages is not None:
            self.pages_allocated += len(pages)
        return pages

    # -- slot table ------------------------------------------------------

    def free_rows(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def occupancy(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def decoding_count(self) -> int:
        return sum(s is not None and s.state == DECODING for s in self.slots)

    def occupied_rows(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    # -- mutations (between chunks only) ---------------------------------

    def admit(self, row: int, request: Request, slot_cache: Dict,
              first_token: int) -> None:
        """Monolithic admission: write a fully-prefilled request into `row`.
        `slot_cache` is a B=1 cache positioned at the prompt length;
        `first_token` the token sampled from the prefill logits (the row's
        first emitted token). On a paged pool the dense slot cache is
        quantized into freshly allocated pages (the caller checked the
        headroom via `pages_for_admission`)."""
        if self.paged:
            pages = self._alloc_pages(
                row, len(request.tokens) // self.engine._block())
            if pages is None:
                raise RuntimeError(
                    f"admit({row}): page headroom vanished between check "
                    "and allocation")
            self.cache = self.engine.write_pool_slot_paged(
                self.cache, slot_cache, row, pages)
        else:
            self.cache = self.engine.write_pool_slot(self.cache, slot_cache,
                                                     row)
        self.cur[row] = first_token
        self.finished[row] = False
        self._rows_dirty = True
        self.slots[row] = _Slot(request=request, emitted=[], state=DECODING,
                                filled=len(request.tokens))

    def begin_prefill(self, row: int, request: Request) -> None:
        """Chunked admission: claim `row` in the PREFILLING state at t=0.
        The row rides subsequent decode chunks finished-masked (its position
        counter frozen, its outputs discarded) while `prefill_chunk_rows` /
        `prefill_remainder_rows` stream the prompt into its cache."""
        self.cache = self.engine.reset_pool_row(self.cache, row)
        self.cur[row] = EOS
        self.finished[row] = True
        self._rows_dirty = True
        self.slots[row] = _Slot(request=request, emitted=[],
                                state=PREFILLING, filled=0)

    def snapshot_rows(self, rows: Sequence[int],
                      tick: int) -> List[SlotSnapshot]:
        """Capture host-side snapshots of occupied `rows` at the current
        chunk boundary (one non-donating padded gather + device_get — the
        cache slice is O(c + M) per row)."""
        subs = self.engine.snapshot_pool_rows(self.cache, rows,
                                              pad_to=self.max_batch)
        if self.paged:
            # worst-case |error| of symmetric round-to-nearest int8 is
            # 0.5·scale per element — accumulate it over the snapshotted
            # page scales as the run's quantization-error telemetry
            for sub in subs:
                for k in ("pages_k_s", "pages_v_s"):
                    # repro-lint: allow[RL002] host snapshot scale leaves
                    s_sum = float(np.asarray(sub[k]).sum())
                    self.quant_error_bound += 0.5 * s_sum
        out = []
        for row, sub in zip(rows, subs):
            slot = self.slots[row]
            out.append(capture(
                rid=slot.request.rid, state=slot.state, filled=slot.filled,
                # repro-lint: allow[RL002] host np mirrors of pool state
                cur=int(self.cur[row]), finished=bool(self.finished[row]),
                emitted=slot.emitted, cache_rows=sub, tick=tick))
        return out

    def restore(self, row: int, request: Request,
                snap: SlotSnapshot) -> None:
        """Re-admit a preempted/faulted request from its snapshot: scatter
        the cache rows back (byte-identical resume) and rebuild the slot.
        A paged restore scatters the snapshot's quantized pages into FRESH
        arena pages — physical placement may differ from capture; the
        table indirection makes the resumed math identical anyway."""
        if self.paged:
            # repro-lint: allow[RL002] snapshot lengths are a host copy
            npv = int(np.asarray(snap.cache_rows["lengths"])[0]) \
                // self.engine._block()
            pages = self._alloc_pages(row, npv)
            if pages is None:
                raise RuntimeError(
                    f"restore({row}): page headroom vanished between check "
                    "and allocation")
            self.cache = self.engine.restore_pool_rows_paged(
                self.cache, snap.cache_rows, row, pages)
        else:
            sub = {k: jnp.asarray(v) for k, v in snap.cache_rows.items()}
            self.cache = self.engine.restore_pool_rows(self.cache, sub, row)
        self.cur[row] = snap.cur
        self.finished[row] = snap.finished
        self._rows_dirty = True
        self.slots[row] = _Slot(request=request, emitted=list(snap.emitted),
                                state=snap.state, filled=snap.filled)

    def scrub_row(self, row: int) -> None:
        """Zero a quarantined row's cache leaves and its position counter.
        A faulty row may hold NaN/Inf — which, unlike finite stale garbage,
        would LEAK through the additive masking of a later occupant's
        attention (NaN + bias = NaN) — so quarantine always scrubs."""
        self.cache = self.engine.scrub_pool_row(self.cache, row)

    def corrupt_row(self, row: int, mode: str) -> None:
        """Fault-injection surface: corrupt row's cache leaves in place
        (mode 'nan' or 'garble') through the donating owner path. On a
        paged pool the corruption hits the row's ring and its OWN pages
        only — neighbour rows' pages stay clean."""
        if self.paged:
            self.cache = self.engine.corrupt_pool_row_paged(
                self.cache, row, self.alloc.pages_of(row), mode)
        else:
            self.cache = self.engine.corrupt_pool_row(self.cache, row, mode)

    def prefill_chunk_rows(self, rows: List[int], tokens: np.ndarray,
                           n_valid: np.ndarray) -> np.ndarray:
        """One padded, batched chunk forward over PREFILLING rows (the
        engine donates the pool cache; the owner swaps in the result).
        The batch is padded to the pool size, so EVERY admission round of
        this pool shares one chunk-forward compile."""
        self.cache, logits = self.engine.pool_prefill_chunk(
            self.cache, rows, tokens, n_valid, pad_to=self.max_batch)
        # repro-lint: allow[RL002] the prefill chunk's one sync
        return np.asarray(logits)

    def prefill_remainder_rows(self, rows: List[int],
                               tokens: np.ndarray) -> np.ndarray:
        """Batched decode-path prefill of the final sub-block remainder
        (pool-size padded like `prefill_chunk_rows`)."""
        self.cache, logits = self.engine.pool_prefill_remainder(
            self.cache, rows, tokens, pad_to=self.max_batch)
        # repro-lint: allow[RL002] the prefill remainder's one sync
        return np.asarray(logits)

    # -- page bookkeeping (paged pools only) ------------------------------

    def pages_for_admission(self, entry: "_QueueEntry") -> int:
        """Pages an entry must be able to allocate AT admission: its
        snapshot's committed pages (restore), the prompt's full blocks
        (monolithic — the whole prefilled prefix lands at once), or none
        (chunked — `ensure_row_pages` grows the table chunk by chunk)."""
        if not self.paged:
            return 0
        c = self.engine._block()
        if entry.snapshot is not None:
            # repro-lint: allow[RL002] snapshot lengths are a host copy
            return int(np.asarray(
                entry.snapshot.cache_rows["lengths"])[0]) // c
        if self.engine.prefill_chunk:
            return 0
        return len(entry.request.tokens) // c

    def ensure_row_pages(self, row: int, target_tokens: int) -> bool:
        """On-demand growth: extend `row`'s page table to cover
        `target_tokens` (ceil to pages) and publish the new entries to the
        device table. Returns False — allocating NOTHING — when the arena
        lacks the pages; the scheduler then preempts or stalls the row."""
        if not self.paged:
            return True
        need = pages_needed(target_tokens, self.engine._block()) \
            - len(self.alloc.pages_of(row))
        if need <= 0:
            return True
        if self._alloc_pages(row, need) is None:
            return False
        self.cache = self.engine.write_table_row(
            self.cache, row, self.alloc.pages_of(row))
        return True

    def activate(self, row: int, first_token: int) -> None:
        """Prefill complete: the row joins the decoding pool next chunk."""
        self.cur[row] = first_token
        self.finished[row] = False
        self._rows_dirty = True
        self.slots[row].state = DECODING

    def retire(self, row: int) -> None:
        if self.paged:
            # clear the device table BEFORE freeing: a stale entry over a
            # re-allocated page would let this dead (finished-masked but
            # still folding) row write into a live tenant's KV bytes
            self.cache = self.engine.clear_table_row(self.cache, row)
            self.alloc.free_row(row)       # scrubs (zeroes) before reuse
        self.slots[row] = None
        self.cur[row] = EOS
        self.finished[row] = True
        self._rows_dirty = True

    def decode_chunk(self, n: int, rng: jax.Array
                     ) -> Tuple[np.ndarray, np.ndarray, jax.Array]:
        """Run one n-step device-resident decode chunk over the pool.
        Returns (tokens (max_batch, n), bad (max_batch,) non-finite-logits
        flags, next rng). The chunk scan donates the pool cache; the
        returned cache replaces it atomically.

        Fast path: between rounds with no row mutation the previous
        chunk's device-resident cur/finished feed the next chunk directly
        (no host->device upload); the host mirrors are still refreshed at
        the chunk's one sync, so scheduler bookkeeping sees exactly the
        values it always did."""
        fn = self._chunk_fns.get(n)
        if fn is None:
            fn = self.engine.pool_chunk_fn(n)
            self._chunk_fns[n] = fn
        if self._rows_dirty or self._cur_dev is None:
            self._cur_dev = jnp.asarray(self.cur)
            self._fin_dev = jnp.asarray(self.finished)
        toks, cur, finished, bad, cache, rng = fn(
            self.engine.params, self._cur_dev, self._fin_dev,
            self.cache, rng)
        self.cache = cache
        self._cur_dev, self._fin_dev = cur, finished
        self._rows_dirty = False
        # repro-lint: allow[RL002] host mirror; rides the chunk sync
        self.cur = np.array(cur)
        # repro-lint: allow[RL002] host mirror; rides the chunk sync
        self.finished = np.array(finished)
        # repro-lint: allow[RL002] the chunk's one sync (decode contract)
        return np.asarray(toks), np.asarray(bad), rng


class Scheduler:
    """SLO-aware continuous-batching scheduler: EDF-within-priority
    admission, preemptive eviction with snapshot resume, bounded-queue
    overload shedding, and fault quarantine/retry. With every knob at its
    default (priority 0, no deadlines, unbounded queue, no injector) the
    behavior is exactly the old FCFS scheduler. See the module docstring
    for the full contract."""

    def __init__(self, engine, max_batch: int,
                 rng: Optional[jax.Array] = None, *,
                 max_queue: Optional[int] = None,
                 max_retries: int = 2,
                 snapshot_chunks: int = 0,
                 nan_guard: bool = True,
                 fault_injector=None,
                 telemetry=None):
        self.engine = engine
        self.pool = SlotPool(engine, max_batch)
        self.waiting: List[_QueueEntry] = []
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.stats = ScheduleStats()
        # fresh per-scheduler timeline namespace + stats registry: warm
        # reruns reuse request ids, so runs must not share either
        self.telemetry = as_telemetry(telemetry)
        self.timelines = self.telemetry.new_timelines("serving")
        self.telemetry.adopt_registry(self.stats.registry, "serving")
        self.max_queue = max_queue
        self.max_retries = max_retries
        # snapshot_chunks=k refreshes every occupied row's last-good
        # snapshot each k-th executed chunk (0 = only capture on
        # preemption; fault recovery then requeues from scratch)
        self.snapshot_chunks = snapshot_chunks
        self.nan_guard = nan_guard
        self.fault_injector = fault_injector
        self.shed: Dict[int, ShedResult] = {}
        self.completed_at: Dict[int, int] = {}      # rid -> completion tick
        self.snapshots: Dict[int, SlotSnapshot] = {}  # row -> last good
        self._streamed: Dict[int, int] = {}  # rid -> streamed high-water
        #                                      mark (a requeued request must
        #                                      not re-stream tokens)
        self._seq = 0
        self._page_stats_last = None  # last published page-gauge tuple:
        #                               the per-round refresh is skipped
        #                               when nothing allocated or freed

    def submit(self, request: Request) -> None:
        """Queue a request. With `max_queue` set, submitting past the bound
        sheds the entry EDF values least (possibly the incoming one) with
        an explicit ShedResult — never silent unbounded queueing."""
        entry = _QueueEntry(request=request, seq=self._seq)
        self._seq += 1
        self.timelines.stamp(request.rid, "queued", self.stats.ticks,
                             priority=request.priority,
                             deadline=request.deadline_ticks,
                             prompt_len=len(request.tokens),
                             budget=request.max_new_tokens)
        if self.max_queue is not None and len(self.waiting) >= self.max_queue:
            victim = max(self.waiting + [entry],
                         key=lambda e: e.sort_key())
            self._shed(victim, SHED_QUEUE_FULL)
            if victim is entry:
                return
            self.waiting.remove(victim)
        self.waiting.append(entry)

    # -- internals -------------------------------------------------------

    def _shed(self, entry: _QueueEntry, reason: str) -> None:
        sr = ShedResult(rid=entry.request.rid, reason=reason,
                        tick=self.stats.ticks,
                        priority=entry.request.priority)
        self.shed[entry.request.rid] = sr
        self.stats.sheds += 1
        self.timelines.stamp(entry.request.rid, "shed", sr.tick,
                             reason=reason)

    def _needed_ticks(self, entry: _QueueEntry) -> int:
        """Optimistic lower bound on ticks to completion if admitted NOW:
        remaining chunked-prefill rounds + remaining decode chunks. Used
        only to shed provably-infeasible deadlines — an optimistic bound
        never sheds a request that could still make it."""
        req = entry.request
        emitted = len(entry.snapshot.emitted) if entry.snapshot else 0
        filled = entry.snapshot.filled if entry.snapshot \
            else (len(req.tokens) if not self.engine.prefill_chunk else 0)
        P = self.engine.prefill_chunk
        prefill_rounds = 0
        if P and filled < len(req.tokens):
            c = self.engine._block()
            nfull = (len(req.tokens) // c) * c
            prefill_rounds = max(0, math.ceil((nfull - filled) / P))
        decode_chunks = math.ceil(
            max(0, req.max_new_tokens - emitted) / self.engine.decode_chunk)
        return prefill_rounds + decode_chunks

    def _arrived(self) -> List[_QueueEntry]:
        """Waiting entries whose arrival time has passed, in EDF order,
        with infeasible-deadline entries shed (the per-round feasibility
        check)."""
        tick = self.stats.ticks
        arrived = [e for e in self.waiting
                   if e.request.arrival_chunk <= tick]
        arrived.sort(key=lambda e: e.sort_key())
        feasible = []
        for e in arrived:
            dl = e.request.deadline_ticks
            if dl is not None and tick + self._needed_ticks(e) > dl:
                self.waiting.remove(e)
                self._shed(e, SHED_DEADLINE_INFEASIBLE)
            elif self.pool.paged and self._lifetime_pages(e.request) \
                    > self.pool.alloc.usable_pages:
                # could never finish even owning the WHOLE arena
                self.waiting.remove(e)
                self._shed(e, SHED_PAGES_EXHAUSTED)
            else:
                feasible.append(e)
        return feasible

    def _lifetime_pages(self, req: Request) -> int:
        """Worst-case pages `req` ever holds at once: full coverage of
        prompt + decode budget."""
        return pages_needed(len(req.tokens) + req.max_new_tokens,
                            self.engine._block())

    def _page_headroom(self, entry: _QueueEntry,
                       extra_free: int = 0) -> bool:
        """Can `entry` allocate its admission pages right now (optionally
        counting a prospective victim's pages as free)?"""
        if not self.pool.paged:
            return True
        return self.pool.pages_for_admission(entry) \
            <= self.pool.alloc.free_pages + extra_free

    def _admit_entry(self, row: int, entry: _QueueEntry) -> None:
        """Place one entry into a free row: snapshot restore (verified by
        checksum) for preempted/faulted entries, else a fresh prefill."""
        self.waiting.remove(entry)
        self.snapshots.pop(row, None)      # stale snapshot of a past tenant
        if entry.snapshot is not None:
            if entry.snapshot.verify():
                self.pool.restore(row, entry.request, entry.snapshot)
                slot = self.pool.slots[row]
                slot.seq, slot.retries = entry.seq, entry.retries
                self.timelines.stamp(entry.request.rid, "restored",
                                     self.stats.ticks, row=row)
                return
            # corrupt snapshot: detected BEFORE its bytes touch the pool;
            # fall back to re-running from the prompt (byte-identical under
            # greedy decode, just slower)
            self.stats.snapshot_corruptions += 1
            entry.snapshot = None
        req = entry.request
        self.timelines.stamp(req.rid, "admitted", self.stats.ticks, row=row)
        if self.engine.prefill_chunk > 0:
            self.pool.begin_prefill(row, req)
        else:
            self.rng, sub = jax.random.split(self.rng)
            with self.telemetry.span("admission_prefill", cat="scheduler",
                                     rid=req.rid, tokens=len(req.tokens)):
                slot_cache, first = self.engine.prefill_request(req.tokens,
                                                                sub)
            self.stats.prefill_forwards += 1      # one B=1 forward each
            self.stats.prefill_tokens += len(req.tokens)
            self.pool.admit(row, req, slot_cache, first)
            self.timelines.stamp(req.rid, "first_token", self.stats.ticks)
        slot = self.pool.slots[row]
        slot.seq, slot.retries = entry.seq, entry.retries

    def _preempt_row(self, row: int) -> None:
        """Evict `row` mid-stream: snapshot its state (chunk boundary, so
        the state is clean) and requeue it with the snapshot attached."""
        slot = self.pool.slots[row]
        snap = self.pool.snapshot_rows([row], self.stats.ticks)[0]
        self.stats.snapshots += 1
        self.timelines.stamp(slot.request.rid, "snapshot", self.stats.ticks,
                             row=row)
        self.waiting.append(_QueueEntry(
            request=slot.request, seq=slot.seq, snapshot=snap,
            retries=slot.retries))
        self.snapshots.pop(row, None)
        self.pool.retire(row)
        self.stats.preemptions += 1
        self.timelines.stamp(slot.request.rid, "preempted", self.stats.ticks,
                             row=row)

    def _admit_ready(self) -> None:
        """Fill free slots with arrived requests in EDF-within-priority
        order, then preempt: while the most urgent still-waiting arrival is
        STRICTLY more urgent than the least-urgent occupied slot, evict
        that slot (snapshot + requeue) and admit the arrival in its place.
        Monolithic mode prefills the whole prompt here (one B=1 forward per
        request); chunked mode only claims the slot — `_advance_prefill`
        streams the prompt in afterwards."""
        arrived = self._arrived()
        for row in self.pool.free_rows():
            if not arrived:
                return
            if not self._page_headroom(arrived[0]):
                # head-of-line blocking on purpose: admitting a later,
                # smaller entry past the most urgent one would invert EDF
                break
            self._admit_entry(row, arrived.pop(0))
        while arrived:
            entry = arrived.pop(0)
            occupied = self.pool.occupied_rows()
            if not occupied:
                break
            victim = max(occupied,
                         key=lambda r: _slot_sort_key(self.pool.slots[r]))
            if _slot_sort_key(self.pool.slots[victim])[0] \
                    <= entry.request.priority:
                break                      # nothing strictly less urgent
            if self.pool.paged and not self._page_headroom(
                    entry, extra_free=len(self.pool.alloc.pages_of(victim))):
                break            # eviction would not free enough pages
            self._preempt_row(victim)
            self._admit_entry(victim, entry)

    def _advance_prefill(self) -> None:
        """Advance every PREFILLING slot by ONE chunk (the interleave
        quantum), batching rows into shared forwards.

        Phase 1 — full-block chunks: every row with ≥ block_size full-block
        prompt tokens left joins ONE padded (g, prefill_chunk) forward —
        per-row `n_valid` + traced per-row offsets mean arbitrary mixes of
        prompt lengths and progress share the compile, which is the whole
        batched-admission win over B=1-per-request monolithic prefill.

        Phase 2 — remainder: rows whose full-block prefix is done feed their
        < block_size leftover tokens through batched decode steps, grouped
        by remainder length (same math as the monolithic path's remainder
        loop, batched).

        Phase 3 — activation: completed rows sample their first token from
        the final logits and flip to DECODING for the next decode chunk."""
        P = self.engine.prefill_chunk
        c = self.engine._block()
        pf = [(row, s) for row, s in enumerate(self.pool.slots)
              if s is not None and s.state == PREFILLING]
        if not pf:
            return
        final_logits: Dict[int, np.ndarray] = {}

        chunk_rows = []
        starved: List[int] = []
        for row, s in pf:
            nfull = (len(s.request.tokens) // c) * c
            if s.filled < nfull:
                n = min(P, nfull - s.filled)
                # on-demand page growth: this chunk folds blocks up to
                # (filled + n)/c — their pages must exist before the fold
                if not self.pool.ensure_row_pages(row, s.filled + n):
                    starved.append(row)    # stalls this round, keeps state
                    continue
                chunk_rows.append((row, s, nfull))
        if chunk_rows:
            g = len(chunk_rows)
            toks = np.zeros((g, P), np.int32)
            n_valid = np.zeros((g,), np.int32)
            for j, (row, s, nfull) in enumerate(chunk_rows):
                n = min(P, nfull - s.filled)
                toks[j, :n] = s.request.tokens[s.filled:s.filled + n]
                n_valid[j] = n
            # repro-lint: allow[RL002] n_valid is a host staging buffer
            chunk_tokens = int(n_valid.sum())
            with self.telemetry.span(
                    "prefill_chunk_forward", cat="scheduler", rows=g,
                    tokens=chunk_tokens,
                    rids=[s.request.rid for _, s, _ in chunk_rows],
                    chunks=[f"{s.filled}:{n}" for (_, s, _), n
                            in zip(chunk_rows, n_valid.tolist())]):
                logits = self.pool.prefill_chunk_rows(
                    [row for row, _, _ in chunk_rows], toks, n_valid)
            self.stats.prefill_forwards += 1
            # repro-lint: allow[RL002] n_valid is a host np staging buffer
            self.stats.prefill_tokens += int(n_valid.sum())
            for j, (row, s, nfull) in enumerate(chunk_rows):
                # repro-lint: allow[RL002] n_valid is a host np staging buffer
                s.filled += int(n_valid[j])
                self.timelines.stamp(s.request.rid, "prefill_chunk",
                                     self.stats.ticks, filled=s.filled,
                                     total=len(s.request.tokens))
                if s.filled == len(s.request.tokens):
                    final_logits[row] = logits[j]

        rem_groups: Dict[int, List[Tuple[int, _Slot]]] = {}
        for row, s in pf:
            rem = len(s.request.tokens) - s.filled
            if 0 < rem < c:
                rem_groups.setdefault(rem, []).append((row, s))
        for rem, group in sorted(rem_groups.items()):
            toks = np.asarray(
                [s.request.tokens[s.filled:s.filled + rem]
                 for _, s in group], np.int32)
            with self.telemetry.span("prefill_remainder_forward",
                                     cat="scheduler", rows=len(group),
                                     tokens=rem * len(group),
                                     rids=[s.request.rid for _, s in group]):
                logits = self.pool.prefill_remainder_rows(
                    [row for row, _ in group], toks)
            self.stats.prefill_forwards += 1
            self.stats.prefill_tokens += rem * len(group)
            for j, (row, s) in enumerate(group):
                s.filled += rem
                self.timelines.stamp(s.request.rid, "prefill_chunk",
                                     self.stats.ticks, filled=s.filled,
                                     total=len(s.request.tokens))
                final_logits[row] = logits[j]

        for row in sorted(final_logits):
            self.rng, sub = jax.random.split(self.rng)
            # repro-lint: allow[RL002] admission first-token sync
            first = int(np.asarray(
                self.engine._sample(jnp.asarray(final_logits[row])[None],
                                    sub))[0])
            self.pool.activate(row, first)
            self.timelines.stamp(self.pool.slots[row].request.rid,
                                 "first_token", self.stats.ticks)

        if starved and not chunk_rows and not rem_groups \
                and self.pool.decoding_count == 0:
            # Nothing in the pool can make progress — every page is tied up
            # by stalled prefills. Preempt the least-urgent page-holding
            # row (its pages are zeroed and freed) so the survivors
            # advance; the victim resumes from its snapshot later.
            holders = [r for r in self.pool.occupied_rows()
                       if self.pool.alloc.pages_of(r)]
            if not holders:
                raise RuntimeError(
                    "page-starved prefill with an empty arena: a single "
                    "chunk outgrows the usable pages (the admission "
                    "feasibility check should have shed this request)")
            victim = max(holders,
                         key=lambda r: _slot_sort_key(self.pool.slots[r]))
            self.stats.page_preemptions += 1
            self._preempt_row(victim)

    def _ensure_decode_pages(self, chunk: int) -> None:
        """Before a decode chunk: grow every DECODING row's page table to
        cover the chunk's folds (on-demand allocation). On exhaustion,
        preempt the least-urgent page-holding row — the needy row itself
        if it IS the least urgent — until the chunk is covered; preempted
        rows resume from their snapshots when pages free up."""
        if not self.pool.paged:
            return
        rows = [(r, s) for r, s in enumerate(self.pool.slots)
                if s is not None and s.state == DECODING]
        for row, s in rows:
            if self.pool.slots[row] is not s:
                continue                   # preempted below, mid-loop
            life = len(s.request.tokens) + s.request.max_new_tokens
            # host upper bound on the row's position: committed prompt +
            # emitted + the pending sampled token (device lengths may lag
            # for finished-masked rows — over-covering by a page is safe)
            target = min(life, s.filled + len(s.emitted) + 1 + chunk)
            while not self.pool.ensure_row_pages(row, target):
                holders = [r for r in self.pool.occupied_rows()
                           if r != row and self.pool.alloc.pages_of(r)]
                victim = row
                if holders:
                    cand = max(holders, key=lambda r: _slot_sort_key(
                        self.pool.slots[r]))
                    if _slot_sort_key(self.pool.slots[cand]) \
                            >= _slot_sort_key(s):
                        victim = cand      # never evict a MORE urgent row
                self.stats.page_preemptions += 1
                self._preempt_row(victim)
                if victim == row:
                    break                  # the row yielded its own slot

    # -- faults ----------------------------------------------------------

    def _capture_snapshots(self) -> None:
        """Refresh every occupied row's last-good snapshot at this chunk
        boundary (one padded gather for the whole pool)."""
        rows = self.pool.occupied_rows()
        if not rows:
            return
        with self.telemetry.span("snapshot_capture", cat="scheduler",
                                 rows=len(rows)):
            snaps = self.pool.snapshot_rows(rows, self.stats.ticks)
        for row, snap in zip(rows, snaps):
            self.snapshots[row] = snap
            self.stats.snapshots += 1
            self.timelines.stamp(snap.rid, "snapshot", self.stats.ticks,
                                 row=row)

    def _quarantine(self, row: int) -> None:
        """Isolate a faulty row: discard its poisoned chunk, scrub the
        row's cache (NaN must never linger where additive masks could leak
        it), and requeue the request from its last good snapshot — or from
        scratch when none exists. Bounded by `max_retries`; exhaustion
        sheds the request explicitly. Neighbour rows are untouched."""
        slot = self.pool.slots[row]
        self.stats.quarantines += 1
        self.timelines.stamp(slot.request.rid, "quarantined",
                             self.stats.ticks, row=row,
                             retries=slot.retries + 1)
        snap = self.snapshots.pop(row, None)
        if snap is not None and snap.rid != slot.request.rid:
            snap = None                    # snapshot of a previous tenant
        entry = _QueueEntry(request=slot.request, seq=slot.seq,
                            snapshot=snap, retries=slot.retries + 1)
        self.pool.retire(row)
        self.pool.scrub_row(row)
        if entry.retries > self.max_retries:
            self._shed(entry, SHED_RETRIES_EXHAUSTED)
            return
        self.stats.retries += 1
        self.waiting.append(entry)

    def _collect_faults(self, bad: np.ndarray) -> Set[int]:
        """Rows to quarantine after a chunk: non-finite-logits flags from
        the device (the NaN guard) plus the injector's failure reports.
        Only live DECODING rows can fault — masked ride-along rows' logits
        are discarded anyway."""
        faulted: Set[int] = set()
        if self.nan_guard:
            for row in np.flatnonzero(bad):
                slot = self.pool.slots[row]
                if slot is not None and slot.state == DECODING:
                    # repro-lint: allow[RL002] host row index
                    faulted.add(int(row))
        if self.fault_injector is not None:
            for row in self.fault_injector.failed_rows(self.stats.chunks):
                if self.pool.slots[row] is not None:
                    # repro-lint: allow[RL002] host row index
                    faulted.add(int(row))
        return faulted

    def _drain_chunk(self, toks: np.ndarray,
                     on_token: Optional[Callable[[int, int], None]],
                     on_complete: Optional[Callable[[int, List[int]], None]],
                     results: Dict[int, List[int]]) -> None:
        """Distribute a chunk's tokens to their requests; retire EOS'd /
        budget-exhausted slots. A requeued request's already-streamed
        tokens are not re-streamed (`_streamed` high-water mark). A
        request's first streamed token is stamped `first_streamed`."""
        for row in range(self.pool.max_batch):
            slot = self.pool.slots[row]
            if slot is None or slot.state != DECODING:
                continue                 # PREFILLING rows rode along masked
            done = False
            rid = slot.request.rid
            budget = slot.request.max_new_tokens
            for tok in toks[row].tolist():
                # budget check BEFORE appending: emit at most `budget`
                if tok == EOS or len(slot.emitted) >= budget:
                    done = True
                    break
                slot.emitted.append(tok)
                n = len(slot.emitted)
                if n > self._streamed.get(rid, 0):
                    self._streamed[rid] = n
                    if n == 1:
                        self.timelines.stamp(rid, "first_streamed",
                                             self.stats.ticks)
                    if on_token is not None:
                        on_token(rid, tok)
            if len(slot.emitted) >= budget:
                done = True
            if done:
                results[rid] = slot.emitted
                self.completed_at[rid] = self.stats.ticks
                dl = slot.request.deadline_ticks
                if dl is not None and self.stats.ticks > dl:
                    self.stats.deadline_misses += 1
                    self.timelines.stamp(rid, "deadline_miss",
                                         self.stats.ticks, deadline=dl)
                self.timelines.stamp(rid, "retired", self.stats.ticks,
                                     n_tokens=len(slot.emitted))
                if on_complete is not None:
                    on_complete(rid, slot.emitted)
                self.snapshots.pop(row, None)
                self.pool.retire(row)

    # -- main loop -------------------------------------------------------

    def run(self,
            on_token: Optional[Callable[[int, int], None]] = None,
            on_complete: Optional[Callable[[int, List[int]], None]] = None,
            ) -> Dict[int, object]:
        """Drive the pool until every submitted request completes or is
        shed. Returns {rid: tokens} (tokens exclude EOS, capped at
        max_new_tokens) with an explicit `ShedResult` in place of the token
        list for rejected requests."""
        results: Dict[int, object] = {}
        chunk = self.engine.decode_chunk
        while self.waiting or self.pool.occupancy:
            # a mark, not a span: callers' telemetry may act on span exits
            # (the benchmark submits due arrivals there)
            decoding = self.pool.decoding_count
            self.telemetry.instant(
                "scheduler_round", cat="scheduler",
                waiting=len(self.waiting),
                prefilling=self.pool.occupancy - decoding, decoding=decoding)
            self._admit_ready()
            if self.engine.prefill_chunk:
                self._advance_prefill()
            self._ensure_decode_pages(chunk)
            if self.pool.paged:
                # page-occupancy gauge + allocation/quant-error telemetry,
                # refreshed when the allocator state changed since the
                # last publish (steady-state decode rounds skip it — part
                # of the scheduler-round fast path)
                page_stats = (self.pool.alloc.used_pages,
                              self.pool.pages_allocated,
                              self.pool.pages_freed,
                              self.pool.quant_error_bound)
                if page_stats != self._page_stats_last:
                    self._page_stats_last = page_stats
                    reg = self.stats.registry
                    reg.gauge("serving_pages_in_use").set(
                        self.pool.alloc.used_pages)
                    reg.gauge("serving_pages_free").set(
                        self.pool.alloc.free_pages)
                    reg.counter("serving_pages_allocated_total").value = \
                        float(self.pool.pages_allocated)
                    reg.counter("serving_pages_freed_total").value = \
                        float(self.pool.pages_freed)
                    reg.counter("serving_quant_error_bound_sum").value = \
                        float(self.pool.quant_error_bound)
            decoding = self.pool.decoding_count
            if not decoding:
                # nothing decodable yet (pool empty, or every occupied slot
                # still prefilling): let virtual time pass so future
                # arrival_chunk requests become admissible
                self.stats.idle_ticks += 1
                continue
            if self.snapshot_chunks and \
                    self.stats.chunks % self.snapshot_chunks == 0:
                self._capture_snapshots()
            if self.fault_injector is not None:
                self.fault_injector.before_chunk(self.pool, self.snapshots,
                                                 self.stats.chunks)
            # one span per chunk, closed at the chunk's single host sync —
            # stamping here adds ZERO device syncs (the sync already exists)
            with self.telemetry.span(
                    "decode_chunk", cat="scheduler", rows=decoding,
                    chunk=chunk, tick=self.stats.ticks,
                    rids=[s.request.rid for s in self.pool.slots
                          if s is not None and s.state == DECODING]):
                toks, bad, self.rng = self.pool.decode_chunk(chunk, self.rng)
            faulted = self._collect_faults(bad)
            self.stats.chunks += 1
            self.stats.row_steps += decoding * chunk
            self.stats.occupancy_sum += self.pool.occupancy \
                / self.pool.max_batch
            for row in sorted(faulted):
                self._quarantine(row)      # retires the row: drain skips it
            self._drain_chunk(toks, on_token, on_complete, results)
        results.update(self.shed)
        # fold raw lifecycle stamps into the per-priority SLO histograms
        # (queue wait, TTFT, TPOT, deadline slack) of this run's registry
        self.timelines.finalize(self.stats.registry)
        return results
