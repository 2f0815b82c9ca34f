"""Continuous-batching scheduler: continuous-vs-static parity, per-row
position-counter decode parity, pool-owner donation safety, streaming."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig, LinformerConfig, ModelConfig
from repro.core.cache import (compressed_decode_attention,
                              full_decode_attention, init_compressed_cache)
from repro.models import model as M
from repro.serving import (Request, Scheduler, ServingEngine, ShedResult,
                           SlotPool)
from tests.conftest import applied_step


def _tiny_cfg(max_seq=64):
    return ModelConfig(
        name="sched-test",
        num_layers=2,
        d_model=32,
        vocab_size=256,
        max_seq_len=max_seq,
        attention=AttentionConfig(
            kind="linformer_causal",
            num_heads=4,
            num_kv_heads=2,          # GQA
            head_dim=8,
            linformer=LinformerConfig(block_size=8, block_slots=4),
        ),
        dtype="float32",
        remat="none",
    )


def _engine(max_seq=64, decode_chunk=4, temperature=0.0, backend=None,
            prefill_chunk=0):
    cfg = _tiny_cfg(max_seq)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, max_seq=max_seq,
                        cache_dtype=jnp.float32, temperature=temperature,
                        decode_chunk=decode_chunk,
                        attention_backend=backend,
                        prefill_chunk=prefill_chunk)
    return eng, cfg, params


def _requests(n=8, seed=0):
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(4, 256, int(rng.choice([8, 9, 16, 19]))))
               for _ in range(n)]
    budgets = [int(rng.choice([3, 6, 10])) for _ in range(n)]
    return prompts, budgets


# ---------------------------------------------------------------------------
# Continuous vs static parity
# ---------------------------------------------------------------------------


class TestContinuousStaticParity:
    def test_shuffled_arrival_order_byte_identical(self):
        """Same request set, shuffled submission order, slot pool ≤ half the
        request count: per-request greedy outputs must be byte-identical to
        the static bucketed baseline."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(8)
        static = eng.serve_static(prompts, budgets, max_batch=4)
        for perm_seed in [1, 2]:
            order = np.random.default_rng(perm_seed).permutation(len(prompts))
            out_perm = eng.serve([prompts[i] for i in order],
                                 [budgets[i] for i in order], max_batch=4)
            for j, i in enumerate(order):
                assert out_perm[j] == static[i], f"request {i} diverged"

    def test_arrival_trace_parity(self):
        """Staggered Poisson-ish arrivals change scheduling, never outputs."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(6, seed=3)
        static = eng.serve_static(prompts, budgets, max_batch=3)
        arrivals = [0, 0, 2, 3, 3, 7]
        cont, sched = eng.serve(prompts, budgets, max_batch=3,
                                arrival_chunks=arrivals,
                                return_scheduler=True)
        assert cont == static
        assert 0.0 < sched.stats.mean_occupancy <= 1.0

    def test_pool_of_one_slot(self):
        """Degenerate pool: pure sequential serving, still identical."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(4, seed=5)
        assert eng.serve(prompts, budgets, max_batch=1) == \
            eng.serve_static(prompts, budgets, max_batch=4)


# ---------------------------------------------------------------------------
# Per-row position counters vs the shared-scalar baseline
# ---------------------------------------------------------------------------


def _layer_cache(B, c=8, r=4, max_seq=32, Hkv=2, Dh=8):
    cache = init_compressed_cache(
        num_layers=1, batch=B, max_seq=max_seq, block_size=c, block_slots=r,
        num_kv_heads=Hkv, head_dim=Dh, dtype=jnp.float32)
    return {k: v[0] for k, v in cache.items() if k != "lengths"}


class TestPerRowLengthsParity:
    EF = jax.random.normal(jax.random.PRNGKey(7), (8, 4)) * 0.3

    def _roll_to(self, t_stop, kvs, backend):
        """Decode a single row (B=1) to position t_stop with scalar t —
        the shared-scalar baseline path."""
        q, k, v = kvs
        lc = _layer_cache(1)
        for t in range(t_stop):
            _, lc = applied_step(
                compressed_decode_attention,
                q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], lc,
                self.EF, self.EF, jnp.int32(t), plan=backend)
        return lc

    @pytest.mark.parametrize("backend", ["reference", "fused"])
    def test_unequal_rows_match_scalar_baseline(self, backend):
        """A batched step at unequal per-row positions — one row exactly at
        the block boundary (its fold must commit), one mid-block, one past a
        completed block — must equal three independent shared-scalar (B=1)
        decodes. GQA: H=4 over Hkv=2."""
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        S, H, Hkv, Dh = 20, 4, 2, 8
        q = jax.random.normal(ks[0], (3, S, H, Dh))
        k = jax.random.normal(ks[1], (3, S, Hkv, Dh))
        v = jax.random.normal(ks[2], (3, S, Hkv, Dh))
        positions = [5, 7, 12]      # mid-block, boundary (c=8), block 1

        # per-row shared-scalar baselines
        row_outs, row_caches = [], []
        for b, t in enumerate(positions):
            kvs = (q[b:b + 1], k[b:b + 1], v[b:b + 1])
            lc = self._roll_to(t, kvs, backend)
            o, lc = applied_step(
                compressed_decode_attention,
                q[b:b + 1, t:t + 1], k[b:b + 1, t:t + 1],
                v[b:b + 1, t:t + 1], lc, self.EF, self.EF, jnp.int32(t),
                plan=backend)
            row_outs.append(o)
            row_caches.append(lc)

        # batched per-row-lengths step from the assembled caches
        lc_b = {key: jnp.concatenate(
            [self._roll_to(t, (q[b:b + 1], k[b:b + 1], v[b:b + 1]),
                           backend)[key]
             for b, t in enumerate(positions)])
            for key in ("raw_k", "raw_v", "comp_k", "comp_v")}
        qs = jnp.stack([q[b, t] for b, t in enumerate(positions)])[:, None]
        kss = jnp.stack([k[b, t] for b, t in enumerate(positions)])[:, None]
        vs = jnp.stack([v[b, t] for b, t in enumerate(positions)])[:, None]
        out_b, lc_b = applied_step(
            compressed_decode_attention,
            qs, kss, vs, lc_b, self.EF, self.EF,
            jnp.asarray(positions, jnp.int32), plan=backend)

        np.testing.assert_allclose(out_b, jnp.concatenate(row_outs),
                                   atol=1e-5)
        for key in lc_b:
            np.testing.assert_allclose(
                lc_b[key],
                jnp.concatenate([rc[key] for rc in row_caches]), atol=1e-5,
                err_msg=key)

    @pytest.mark.parametrize("backend", ["reference", "fused"])
    def test_scalar_broadcasts_to_vector(self, backend):
        """t given as () and as a constant (B,) vector are the same step."""
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, 1, 4, 8))
        k = jax.random.normal(ks[1], (2, 1, 2, 8))
        v = jax.random.normal(ks[2], (2, 1, 2, 8))
        lc = _layer_cache(2)
        o_s, c_s = applied_step(
            compressed_decode_attention,
            q, k, v, lc, self.EF, self.EF, jnp.int32(3), plan=backend)
        o_v, c_v = applied_step(
            compressed_decode_attention,
            q, k, v, lc, self.EF, self.EF, jnp.full((2,), 3, jnp.int32),
            plan=backend)
        np.testing.assert_array_equal(o_s, o_v)
        for key in c_s:
            np.testing.assert_array_equal(c_s[key], c_v[key])

    def test_full_cache_unequal_rows(self):
        """Standard-attention decode with per-row t matches per-row B=1."""
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        B, S, H, Hkv, Dh = 2, 16, 4, 2, 8
        cache_k = jax.random.normal(ks[0], (B, S, Hkv, Dh))
        cache_v = jax.random.normal(ks[1], (B, S, Hkv, Dh))
        q = jax.random.normal(ks[2], (B, 1, H, Dh))
        k = jax.random.normal(ks[3], (B, 1, Hkv, Dh))
        v = jax.random.normal(ks[4], (B, 1, Hkv, Dh))
        ts = jnp.asarray([4, 11], jnp.int32)
        out_b, cb = applied_step(
            full_decode_attention,
            q, k, v, {"k": cache_k, "v": cache_v}, ts)
        for b in range(B):
            out_1, c1 = applied_step(
                full_decode_attention,
                q[b:b + 1], k[b:b + 1], v[b:b + 1],
                {"k": cache_k[b:b + 1], "v": cache_v[b:b + 1]},
                jnp.int32(int(ts[b])))
            np.testing.assert_allclose(out_b[b:b + 1], out_1, atol=1e-6)
            np.testing.assert_allclose(cb["k"][b:b + 1], c1["k"], atol=1e-6)


# ---------------------------------------------------------------------------
# Scheduler mechanics
# ---------------------------------------------------------------------------


class TestSchedulerMechanics:
    def test_streaming_callbacks(self):
        """on_token streams every output token in order; on_complete fires
        exactly once per request with the full output."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(5, seed=9)
        streamed = {i: [] for i in range(len(prompts))}
        completed = {}
        outs = eng.serve(prompts, budgets, max_batch=2,
                         on_token=lambda rid, tok: streamed[rid].append(tok),
                         on_complete=lambda rid, toks: completed.setdefault(
                             rid, list(toks)))
        for i, o in enumerate(outs):
            assert streamed[i] == o
            assert completed[i] == o

    def test_arrivals_respected(self):
        """A request is never admitted before its arrival chunk."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(3, seed=11)
        sched = Scheduler(eng, max_batch=2)
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=i, tokens=tuple(p),
                                 max_new_tokens=budgets[i],
                                 arrival_chunk=[0, 0, 4][i]))
        admitted_at = {}
        orig_admit = sched.pool.admit

        def admit(row, req, cache, first):
            admitted_at[req.rid] = sched.stats.ticks
            orig_admit(row, req, cache, first)

        sched.pool.admit = admit
        sched.run()
        assert admitted_at[2] >= 4
        assert admitted_at[0] == admitted_at[1] == 0

    def test_budget_exceeding_max_seq_rejected(self):
        eng, _, _ = _engine(max_seq=32)
        with pytest.raises(ValueError, match="max_seq"):
            eng.serve([[1] * 24], max_new_tokens=16, max_batch=2)
        with pytest.raises(ValueError, match="max_seq"):
            eng.serve_static([[1] * 24], max_new_tokens=16, max_batch=2)

    def test_zero_budget_rejected(self):
        """max_new_tokens <= 0 fails fast at submission on both schedulers
        (a request that can emit nothing is a caller bug, not a no-op)."""
        eng, _, _ = _engine()
        prompts, _ = _requests(3, seed=15)
        budgets = [0, 4, 0]
        with pytest.raises(ValueError, match="request 0.*max_new_tokens"):
            eng.serve(prompts, budgets, max_batch=2)
        with pytest.raises(ValueError, match="request 0.*max_new_tokens"):
            eng.serve_static(prompts, budgets, max_batch=2)

    def test_pool_requires_per_row_lengths(self):
        """Model families with a shared scalar cache can't pool-schedule."""

        class ScalarEngine:
            def init_pool_cache(self, n):
                return {"k": jnp.zeros((1, n, 4, 2, 8)),
                        "length": jnp.zeros((), jnp.int32)}

        with pytest.raises(ValueError, match="serve_static"):
            SlotPool(ScalarEngine(), 4)

    def test_pool_owner_survives_donation(self):
        """The chunk scan donates the pool cache; the SlotPool owner swaps in
        the returned buffers, so repeated serves on one engine (and direct
        decode_tokens use in between) never touch a donated array."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(4, seed=13)
        first = eng.serve(prompts, budgets, max_batch=2)
        # interleave a batch-level decode (its own donated cache)
        toks = np.asarray([prompts[0][:8], prompts[1][:8]], np.int32)
        eng.generate_batch(toks, 4)
        second, sched = eng.serve(prompts, budgets, max_batch=2,
                                  return_scheduler=True)
        assert first == second
        # the owner's cache is live (donation replaced, not invalidated)
        assert np.asarray(sched.pool.cache["lengths"]).shape == (2,)


# ---------------------------------------------------------------------------
# Preemption: evict-and-requeue with byte-identical resume
# ---------------------------------------------------------------------------


class TestPreemption:
    @pytest.mark.parametrize("backend", ["reference", "fused"])
    def test_preempt_resume_byte_identical(self, backend):
        """Property test: low-priority requests running first, high-priority
        arrivals displacing them mid-stream, shuffled submission order — a
        preempted request's snapshot-restored resume must be byte-identical
        to an uninterrupted run (the static baseline), on both kernel
        backends."""
        eng, _, _ = _engine(backend=backend)
        prompts, budgets = _requests(8, seed=21)
        static = eng.serve_static(prompts, budgets, max_batch=4)
        order = list(np.random.default_rng(2).permutation(len(prompts)))
        out, sched = eng.serve(
            [prompts[i] for i in order], [budgets[i] for i in order],
            max_batch=2,
            # late arrivals are strictly more urgent: they must preempt
            priorities=[3, 3, 3, 3, 0, 0, 0, 0],
            arrival_chunks=[0, 0, 0, 0, 2, 2, 3, 3],
            return_scheduler=True)
        assert sched.stats.preemptions > 0
        for j, i in enumerate(order):
            assert out[j] == static[i], f"request {i} diverged"

    def test_one_slot_pool_preemption(self):
        """Degenerate 1-slot pool: every high-priority arrival preempts THE
        slot; the victim bounces back and forth and must still finish
        byte-identically."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(4, seed=23)
        static = eng.serve_static(prompts, budgets, max_batch=4)
        out, sched = eng.serve(prompts, budgets, max_batch=1,
                               priorities=[2, 1, 1, 0],
                               arrival_chunks=[0, 1, 2, 3],
                               return_scheduler=True)
        assert sched.stats.preemptions > 0
        assert out == static

    def test_chunked_prefill_preemption(self):
        """A PREFILLING slot can be preempted mid-prompt; its snapshot
        carries the prefill progress and resumes without re-reading
        committed chunks."""
        eng, _, _ = _engine(prefill_chunk=8)
        prompts, budgets = _requests(8, seed=25)
        static = eng.serve_static(prompts, budgets, max_batch=4)
        out, sched = eng.serve(prompts, budgets, max_batch=2,
                               priorities=[3, 3, 2, 2, 1, 1, 0, 0],
                               arrival_chunks=[0, 0, 1, 1, 2, 2, 3, 3],
                               return_scheduler=True)
        assert sched.stats.preemptions > 0
        assert out == static

    def test_equal_priority_never_preempts(self):
        """Preemption needs STRICT urgency: same-priority arrivals wait for
        a free slot (no thrash between peers)."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(6, seed=27)
        out, sched = eng.serve(prompts, budgets, max_batch=2,
                               priorities=[1] * 6,
                               arrival_chunks=[0, 0, 1, 2, 3, 4],
                               return_scheduler=True)
        assert sched.stats.preemptions == 0
        assert out == eng.serve_static(prompts, budgets, max_batch=4)


# ---------------------------------------------------------------------------
# SLO scheduling: EDF ordering, bounded queue, deadlines
# ---------------------------------------------------------------------------


class TestSLOScheduling:
    def test_priority_classes_order_admission(self):
        """With one slot and simultaneous arrivals, admission follows
        priority classes (then submission order)."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(4, seed=31)
        completed = []
        eng.serve(prompts, budgets, max_batch=1,
                  priorities=[2, 0, 1, 0],
                  on_complete=lambda rid, toks: completed.append(rid))
        assert completed == [1, 3, 2, 0]

    def test_edf_within_class(self):
        """Same priority: the earlier deadline runs first."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(3, seed=33)
        completed = []
        eng.serve(prompts, budgets, max_batch=1,
                  deadlines=[None, 50, 200],
                  on_complete=lambda rid, toks: completed.append(rid))
        assert completed[0] == 1          # deadline 50 beats 200 and None

    def test_bounded_queue_sheds_least_urgent(self):
        """Submissions beyond max_queue shed the least-valued entry with an
        explicit ShedResult — never silent unbounded queueing — and every
        admitted request still completes byte-identically."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(8, seed=35)
        static = eng.serve_static(prompts, budgets, max_batch=4)
        out, sched = eng.serve(prompts, budgets, max_batch=2, max_queue=3,
                               priorities=[0, 0, 1, 1, 2, 2, 2, 2],
                               return_scheduler=True)
        shed = [o for o in out if isinstance(o, ShedResult)]
        assert shed and sched.stats.sheds == len(shed)
        assert all(o.reason == "queue_full" for o in shed)
        # shedding picks the least-valued entry KNOWN AT SUBMIT TIME, so
        # later low-priority arrivals can't retroactively save an earlier
        # victim — but the most urgent class is never shed
        assert all(o.priority >= 1 for o in shed)
        for o, s in zip(out, static):
            assert isinstance(o, ShedResult) or o == s

    def test_infeasible_deadline_shed_not_admitted(self):
        """A deadline that cannot be met even by the optimistic estimate is
        shed at admission, not admitted to fail."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(2, seed=37)
        out, sched = eng.serve(prompts, budgets, max_batch=2,
                               deadlines=[None, 0],
                               return_scheduler=True)
        assert isinstance(out[1], ShedResult)
        assert out[1].reason == "deadline_infeasible"
        assert sched.stats.deadline_misses == 0

    def test_deadline_met_not_counted_missed(self):
        """Generous deadlines complete with zero misses and no sheds."""
        eng, _, _ = _engine()
        prompts, budgets = _requests(4, seed=39)
        out, sched = eng.serve(prompts, budgets, max_batch=4,
                               deadlines=[1000] * 4,
                               return_scheduler=True)
        assert sched.stats.deadline_misses == 0
        assert sched.stats.sheds == 0
        assert out == eng.serve_static(prompts, budgets, max_batch=4)

    def test_counters_line_mentions_every_counter(self):
        stats = Scheduler(_engine()[0], max_batch=1).stats
        line = stats.counters_line()
        for name in ("preemptions", "sheds", "deadline_misses", "retries",
                     "quarantines"):
            assert name in line
