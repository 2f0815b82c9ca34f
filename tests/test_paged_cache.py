"""Tolerance-banded low-precision parity suite for the paged, quantized
pool cache.

The paged cache stores the ring and the compressed page arena in int8 (or
fp8 where the jnp build supports it) with per-block fp32 scales, dequantized
inside the fused kernels. Quantization is the ONLY intended divergence from
the dense fp32 cache, so this suite pins three contracts:

* **Tolerance bands** (`DECODE_TOL` / `PREFILL_TOL`): paged decode/prefill
  attention vs the dense fp32 oracle stays inside a per-storage-dtype band.
  The bands are documented in docs/serving.md; measured worst-case error at
  the suite's shapes is ~0.013 (int8), so the 0.05 band has ~4x headroom
  without masking real regressions (a missing scale shows up as O(1)).
* **Backend parity** (`FUSED_TOL`): the fused Pallas kernels, which
  dequantize in VMEM, match the reference jnp path (which dequantizes
  up front) on IDENTICAL quantized operands — so the bands above measure
  quantization, never kernel bugs.
* **The chunked-admission rounding contract**: a prefill chunk attends
  earlier blocks CACHE-ROUNDED (dequantized pages), exactly — the same
  contract tests/test_chunked_prefill.py characterizes for the dense
  low-precision cache, one notch coarser.

Engine-level legs cover GQA (all configs here use Hkv < H), fold-boundary
prompt lengths, preempt/restore byte-identity under page pressure, and the
`pages_exhausted` shed reason.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig, LinformerConfig, ModelConfig
from repro.core import cache as cache_lib
from repro.core.causal import blockwise_causal_prefix_attention
from repro.models import model as M
from repro.serving import ServingEngine, ShedResult
from repro.serving.scheduler import SHED_PAGES_EXHAUSTED
from tests.conftest import applied_step

# Documented per-storage-dtype tolerance bands (max |paged - dense fp32|
# attention output, pre-softmax inputs O(1) normal). int8 rounds to
# 0.5/127 of each block's amax; fp8 e4m3 carries 3 mantissa bits, so its
# band is ~4x wider. docs/serving.md quotes these numbers.
DECODE_TOL = {"int8": 0.05, "fp8": 0.2}
PREFILL_TOL = {"int8": 0.05, "fp8": 0.2}
# fused-vs-reference on identical quantized operands: pure fp32 math
# reassociation, no quantization term.
FUSED_TOL = 1e-5

HAS_FP8 = getattr(jnp, "float8_e4m3fn", None) is not None
PAGE_DTYPES = ["int8"] + (["fp8"] if HAS_FP8 else [])

B, H, HKV, DH = 2, 4, 2, 8           # GQA: 2 query heads share each kv head
C, R, MAXP = 8, 4, 8                 # page = one fold of C tokens -> R slots
M_SLOTS = MAXP * R


def _inputs(S, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, DH))
    k = jax.random.normal(ks[1], (B, S, HKV, DH))
    v = jax.random.normal(ks[2], (B, S, HKV, DH))
    E = jax.random.normal(ks[3], (C, R)) * 0.3
    F = jax.random.normal(ks[4], (C, R)) * 0.3
    return q, k, v, E, F


def _dense_layer_cache():
    f32 = jnp.float32
    return {"raw_k": jnp.zeros((B, C, HKV, DH), f32),
            "raw_v": jnp.zeros((B, C, HKV, DH), f32),
            "comp_k": jnp.zeros((B, M_SLOTS, HKV, DH), f32),
            "comp_v": jnp.zeros((B, M_SLOTS, HKV, DH), f32)}


def _paged_layer_cache(page_dtype="int8", table="full"):
    """Single-layer paged cache slice. `table="full"` pre-allocates row b's
    pages as b*MAXP..(b+1)*MAXP-1 (the serving layer does this dynamically);
    `table="empty"` leaves every block unallocated (-1)."""
    n_pages = B * MAXP + 1                    # + TRASH
    pdt, _ = cache_lib.resolve_page_dtype(page_dtype)
    f32 = jnp.float32
    if table == "full":
        tab = jnp.arange(B * MAXP, dtype=jnp.int32).reshape(B, MAXP)
    else:
        tab = jnp.full((B, MAXP), -1, jnp.int32)
    return {"raw_k_q": jnp.zeros((B, C, HKV, DH), pdt),
            "raw_v_q": jnp.zeros((B, C, HKV, DH), pdt),
            "raw_k_s": jnp.zeros((B, C, HKV), f32),
            "raw_v_s": jnp.zeros((B, C, HKV), f32),
            "page_k": jnp.zeros((n_pages, R, HKV, DH), pdt),
            "page_v": jnp.zeros((n_pages, R, HKV, DH), pdt),
            "page_k_s": jnp.zeros((n_pages, HKV), f32),
            "page_v_s": jnp.zeros((n_pages, HKV), f32),
            "page_table": tab}


def _stream(S, *, plan="reference", page_dtype="int8", t0=None, seed=0):
    """Decode S tokens through BOTH caches (identical inputs), collecting
    per-step attention outputs. `t0` (B,) offsets rows to unequal positions
    — the continuous-batching case where every per-row (pos, blk) combo is
    live at once."""
    q, k, v, E, F = _inputs(S, seed=seed)
    dlc, plc = _dense_layer_cache(), _paged_layer_cache(page_dtype)
    base = jnp.zeros((B,), jnp.int32) if t0 is None else jnp.asarray(t0)
    outs_d, outs_p = [], []
    for t in range(S):
        tt = base + t
        sl = (q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1])
        od, dlc = applied_step(
            cache_lib.compressed_decode_attention,
            *sl, dlc, E, F, tt, plan="reference")
        op, plc = applied_step(
            cache_lib.paged_decode_attention,
            *sl, plc, E, F, tt, plan=plan)
        outs_d.append(od)
        outs_p.append(op)
    return (np.asarray(jnp.concatenate(outs_d, axis=1)),
            np.asarray(jnp.concatenate(outs_p, axis=1)), plc)


# ---------------------------------------------------------------------------
# Quantization primitives
# ---------------------------------------------------------------------------


class TestQuantization:
    def test_int8_roundtrip_error_bound(self):
        """Symmetric round-to-nearest int8: per-element reconstruction error
        is <= 0.5 * that block's scale — the bound the serving telemetry
        accumulates as `serving_quant_error_bound_sum`."""
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 2, 8))
        q, s = cache_lib.quantize_blockwise(x, (3,))
        deq = cache_lib.dequantize_blockwise(q, s)
        err = np.abs(np.asarray(deq) - np.asarray(x))
        bound = 0.5 * np.asarray(s)[..., None]
        assert (err <= bound + 1e-7).all()

    def test_scale_covers_amax(self):
        """qmax * scale >= amax: the block extreme is representable, so
        clipping never bites on the quantizer's own input."""
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 4)) * 100.0
        q, s = cache_lib.quantize_blockwise(x, (2,))
        amax = np.abs(np.asarray(x)).max(axis=2)
        assert (127.0 * np.asarray(s) >= amax - 1e-5).all()
        assert (np.abs(np.asarray(q, np.int32)) <= 127).all()

    def test_zero_block_safe(self):
        """An all-zero block quantizes to zeros with a tiny positive scale
        (no 0/0 NaN), and dequantizes back to exact zeros."""
        x = jnp.zeros((2, 8, 4))
        q, s = cache_lib.quantize_blockwise(x, (2,))
        assert np.isfinite(np.asarray(s)).all() and (np.asarray(s) > 0).all()
        assert (np.asarray(cache_lib.dequantize_blockwise(q, s)) == 0).all()

    def test_resolve_page_dtype(self):
        dt, qmax = cache_lib.resolve_page_dtype("int8")
        assert dt == jnp.int8 and qmax == 127.0
        with pytest.raises(ValueError, match="int8|fp8"):
            cache_lib.resolve_page_dtype("int4")
        if HAS_FP8:
            dt, qmax = cache_lib.resolve_page_dtype("fp8")
            assert qmax == 448.0
        else:
            with pytest.raises(ValueError, match="float8"):
                cache_lib.resolve_page_dtype("fp8")

    @pytest.mark.skipif(not HAS_FP8, reason="no jnp.float8_e4m3fn")
    def test_fp8_roundtrip_relative_error(self):
        """fp8 e4m3 (3 mantissa bits): relative reconstruction error per
        element stays under 2^-3 of the block amax."""
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 8))
        fp8 = jnp.float8_e4m3fn
        q, s = cache_lib.quantize_blockwise(x, (2,), dtype=fp8, qmax=448.0)
        deq = cache_lib.dequantize_blockwise(q, s)
        amax = np.abs(np.asarray(x)).max(axis=2, keepdims=True)
        err = np.abs(np.asarray(deq) - np.asarray(x))
        assert (err <= amax * 2.0 ** -3 + 1e-6).all()


# ---------------------------------------------------------------------------
# Decode parity: paged quantized vs dense fp32, and fused vs reference
# ---------------------------------------------------------------------------


class TestDecodeParity:
    @pytest.mark.parametrize("page_dtype", PAGE_DTYPES)
    def test_quantized_vs_fp32_band(self, page_dtype):
        """40 decode steps (5 full folds): every step's paged output is
        inside the storage dtype's band of the dense fp32 oracle."""
        outs_d, outs_p, _ = _stream(40, page_dtype=page_dtype)
        err = np.abs(outs_p - outs_d).max()
        assert err <= DECODE_TOL[page_dtype], \
            f"{page_dtype} decode error {err} exceeds band"

    @pytest.mark.parametrize("page_dtype", PAGE_DTYPES)
    def test_per_row_offsets(self, page_dtype):
        """Rows at unequal positions (t0 = [0, 16]): per-row masks, folds
        and page scatters stay inside the band — no cross-row mixing."""
        outs_d, outs_p, _ = _stream(
            17, page_dtype=page_dtype, t0=[0, 16], seed=3)
        err = np.abs(outs_p - outs_d).max()
        assert err <= DECODE_TOL[page_dtype]

    def test_fused_matches_reference(self):
        """Fused kernel (dequant in VMEM) vs reference (dequant in jnp) on
        identical quantized caches: fp32-reassociation-only difference, and
        the updated caches are byte-identical (bookkeeping is shared)."""
        _, ref, plc_ref = _stream(24, plan="reference", seed=1)
        _, fus, plc_fus = _stream(24, plan="fused", seed=1)
        assert np.abs(fus - ref).max() <= FUSED_TOL
        for key in plc_ref:
            np.testing.assert_array_equal(np.asarray(plc_ref[key]),
                                          np.asarray(plc_fus[key]), key)

    def test_trash_page_never_read(self):
        """Poisoning the TRASH page (saturated payloads, huge scales) must
        not change any output: TRASH is written by redirected folds but
        never becomes visible."""
        q, k, v, E, F = _inputs(24, seed=4)
        clean = _paged_layer_cache()
        poisoned = dict(clean)
        trash = clean["page_k"].shape[0] - 1
        poisoned["page_k"] = clean["page_k"].at[trash].set(127)
        poisoned["page_v"] = clean["page_v"].at[trash].set(-127)
        poisoned["page_k_s"] = clean["page_k_s"].at[trash].set(1e6)
        poisoned["page_v_s"] = clean["page_v_s"].at[trash].set(1e6)
        for t in range(24):
            sl = (q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1])
            oc, clean = applied_step(
                cache_lib.paged_decode_attention,
                *sl, clean, E, F, jnp.full((B,), t, jnp.int32))
            op, poisoned = applied_step(
                cache_lib.paged_decode_attention,
                *sl, poisoned, E, F, jnp.full((B,), t, jnp.int32))
            np.testing.assert_array_equal(np.asarray(oc), np.asarray(op))

    def test_unallocated_fold_redirects_to_trash(self):
        """With an all-unallocated table, a completed fold lands on TRASH
        and every real arena page stays zero — device code never allocates,
        and a missing page can't corrupt a neighbour."""
        q, k, v, E, F = _inputs(8, seed=5)
        plc = _paged_layer_cache(table="empty")
        for t in range(8):
            _, plc = applied_step(
                cache_lib.paged_decode_attention,
                q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1],
                plc, E, F, jnp.full((B,), t, jnp.int32))
        pages_k = np.asarray(plc["page_k"])
        assert (pages_k[:-1] == 0).all()       # all real pages untouched
        assert (pages_k[-1] != 0).any()        # the fold DID go somewhere


# ---------------------------------------------------------------------------
# Prefill-chunk parity + the chunked-admission rounding contract
# ---------------------------------------------------------------------------


class TestPrefillParity:
    def _run_chunks(self, plan, page_dtype, S=32, P=16, seed=2):
        q, k, v, E, F = _inputs(S, seed=seed)
        dlc, plc = _dense_layer_cache(), _paged_layer_cache(page_dtype)
        outs_d, outs_p = [], []
        for t0 in range(0, S, P):
            tt = jnp.full((B,), t0, jnp.int32)
            sl = (q[:, t0:t0 + P], k[:, t0:t0 + P], v[:, t0:t0 + P])
            od, dlc = applied_step(
                cache_lib.compressed_prefill_chunk,
                *sl, dlc, E, F, tt, plan="reference")
            op, plc = applied_step(
                cache_lib.paged_prefill_chunk,
                *sl, plc, E, F, tt, plan=plan)
            outs_d.append(od)
            outs_p.append(op)
        return (np.asarray(jnp.concatenate(outs_d, axis=1)),
                np.asarray(jnp.concatenate(outs_p, axis=1)), plc)

    @pytest.mark.parametrize("page_dtype", PAGE_DTYPES)
    def test_quantized_vs_fp32_band(self, page_dtype):
        outs_d, outs_p, _ = self._run_chunks("reference", page_dtype)
        err = np.abs(outs_p - outs_d).max()
        assert err <= PREFILL_TOL[page_dtype], \
            f"{page_dtype} prefill error {err} exceeds band"

    def test_fused_matches_reference(self):
        _, ref, plc_ref = self._run_chunks("reference", "int8")
        _, fus, plc_fus = self._run_chunks("fused", "int8")
        assert np.abs(fus - ref).max() <= FUSED_TOL
        for key in plc_ref:
            np.testing.assert_array_equal(np.asarray(plc_ref[key]),
                                          np.asarray(plc_fus[key]), key)

    def test_rounding_contract_is_exactly_dequantized_pages(self):
        """The chunked-admission rounding contract, characterized: chunk 2's
        paged output equals BITWISE the dense prefix attention computed over
        the dequantized post-scatter page gather. Quantization of the
        visible prefix is the whole contract — there is no other divergence
        source (the dense-cache analogue lives in
        tests/test_chunked_prefill.py::TestPrefixAttentionParity)."""
        q, k, v, E, F = _inputs(32, seed=6)
        plc = _paged_layer_cache()
        _, plc = applied_step(
            cache_lib.paged_prefill_chunk,
            q[:, :16], k[:, :16], v[:, :16], plc, E, F,
            jnp.zeros((B,), jnp.int32))
        out, plc = applied_step(
            cache_lib.paged_prefill_chunk,
            q[:, 16:], k[:, 16:], v[:, 16:], plc, E, F,
            jnp.full((B,), 16, jnp.int32))
        gk, gk_s = cache_lib.paged_gather(
            plc["page_k"], plc["page_k_s"], plc["page_table"])
        gv, gv_s = cache_lib.paged_gather(
            plc["page_v"], plc["page_v_s"], plc["page_table"])
        want = blockwise_causal_prefix_attention(
            q[:, 16:], k[:, 16:], v[:, 16:],
            cache_lib.dequantize_blockwise(gk, gk_s),
            cache_lib.dequantize_blockwise(gv, gv_s),
            jnp.full((B,), 2, jnp.int32), block_size=C, block_slots=R,
            scale=DH ** -0.5)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---------------------------------------------------------------------------
# Engine-level: GQA serving on fold-boundary lengths, preemption, shedding
# ---------------------------------------------------------------------------


def _cfg(max_seq=160):
    attn = AttentionConfig(
        kind="linformer_causal",
        backend="auto",
        num_heads=4,
        num_kv_heads=2,              # GQA on every engine leg
        head_dim=8,
        linformer=LinformerConfig(block_size=8, block_slots=4),
    )
    return ModelConfig(name="paged-cache-test", num_layers=2, d_model=32,
                       vocab_size=256, max_seq_len=max_seq, attention=attn,
                       dtype="float32", remat="none")


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(0), _cfg())


def _paged_engine(params, prefill_chunk=0, **kw):
    return ServingEngine(params, _cfg(), max_seq=160,
                         cache_dtype=jnp.float32, decode_chunk=4,
                         prefill_chunk=prefill_chunk, cache_format="paged",
                         **kw)


# fold-boundary coverage: < one block (5), exact block (8), mid-block (12),
# exact fold multiples (16, 32), fold+remainder (19, 40), long (61, 80)
LENS = [5, 8, 12, 16, 19, 32, 40, 61, 80, 24]


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(4, 256, L)) for L in LENS]
    budgets = [int(rng.choice([3, 6, 10])) for _ in LENS]
    return prompts, budgets


class TestPagedEngine:
    def test_serve_deterministic_and_leak_free(self, params):
        """Paged serve over fold-boundary lengths: repeatable outputs, the
        allocator's partition invariant holds afterwards, and every page
        came back (retire frees + scrubs)."""
        eng = _paged_engine(params)
        prompts, budgets = _prompts()
        out, sched = eng.serve(prompts, budgets, max_batch=4,
                               return_scheduler=True)
        assert all(o and not isinstance(o, ShedResult) for o in out)
        alloc = sched.pool.alloc
        alloc.check()
        assert alloc.free_pages == alloc.usable_pages
        assert sched.pool.pages_allocated == sched.pool.pages_freed > 0
        assert eng.serve(prompts, budgets, max_batch=4) == out

    def test_chunked_admission_rounding_contract(self, params):
        """Chunked vs monolithic admission on the SAME paged engine params:
        both modes complete, and the agreed-fraction floor documents the
        rounding contract at token granularity — divergence only where a
        near-tie argmax flips under the (deterministic) quantized-prefix
        rounding. Seeds are fixed, so this is exact, not statistical."""
        prompts, budgets = _prompts()
        mono = _paged_engine(params).serve(prompts, budgets, max_batch=4)
        chun = _paged_engine(params, prefill_chunk=16).serve(
            prompts, budgets, max_batch=4)
        agree = sum(a == b for a, b in zip(mono, chun))
        assert agree >= len(LENS) // 2, (mono, chun)
        assert all(len(o) == b for o, b in zip(chun, budgets))

    @pytest.mark.parametrize("prefill_chunk", [0, 16])
    def test_preempt_restore_byte_identical_under_page_pressure(
            self, params, prefill_chunk):
        """A page-tight arena forces page preemptions mid-decode; with
        snapshots enabled the preempted rows resume from quantized
        snapshots into FRESH physical pages — outputs must equal the
        uncontended run byte-for-byte (the table indirection makes physical
        placement invisible to the math)."""
        prompts, budgets = _prompts(seed=1)
        want = _paged_engine(params, prefill_chunk).serve(
            prompts, budgets, max_batch=4)
        tight = _paged_engine(params, prefill_chunk, arena_pages=14)
        out, sched = tight.serve(prompts, budgets, max_batch=4,
                                 snapshot_chunks=2, return_scheduler=True)
        assert out == want
        assert sched.stats.page_preemptions > 0
        sched.pool.alloc.check()
        assert sched.pool.alloc.free_pages == sched.pool.alloc.usable_pages

    def test_lifetime_infeasible_request_shed(self, params):
        """A request whose prompt+budget can NEVER fit the arena is shed
        with the explicit pages_exhausted reason instead of wedging the
        admission queue."""
        eng = _paged_engine(params, arena_pages=4)   # 3 usable pages
        prompts = [[1] * 40, [2] * 8]                # 40+6 needs 6 pages
        out = eng.serve(prompts, [6, 3], max_batch=2)
        assert isinstance(out[0], ShedResult)
        assert out[0].reason == SHED_PAGES_EXHAUSTED
        assert not isinstance(out[1], ShedResult)    # 8+3 fits in 2 pages

    @pytest.mark.skipif(not HAS_FP8, reason="no jnp.float8_e4m3fn")
    def test_fp8_engine_serves(self, params):
        """fp8 page storage end-to-end where supported: deterministic serve
        and clean page accounting (the parity band for fp8 is pinned at the
        cache level above)."""
        eng = _paged_engine(params, page_dtype="fp8")
        prompts, budgets = _prompts(seed=2)
        out, sched = eng.serve(prompts, budgets, max_batch=4,
                               return_scheduler=True)
        assert all(o and not isinstance(o, ShedResult) for o in out)
        sched.pool.alloc.check()
        assert eng.serve(prompts, budgets, max_batch=4) == out

    def test_fp8_requires_support(self, params):
        if HAS_FP8:
            pytest.skip("build has fp8; the negative leg is above")
        with pytest.raises(ValueError, match="float8"):
            _paged_engine(params, page_dtype="fp8")
