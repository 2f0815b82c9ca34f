"""Fused blockwise-causal Linformer attention Pallas kernels (TPU target).

Forward — one grid step computes one query block (c tokens of one
(batch, head)): joint softmax over [own block, causal | compressed slots of
previous blocks]. The compressed K̄/V̄ (M = (S/c)·r slots) are pinned in
VMEM — at r/c = 16/256 compression, a 32k-token context compresses to 2048
slots × Dh (512 KiB bf16), far under VMEM; raw K/V of the own block are
streamed per grid step.

Grid: (B·H, nb). Blocks:
  q, k_loc, v_loc : (1, c, Dh)   — block `n` of the sequence
  k̄, v̄           : (1, M, Dh)   — pinned
  out             : (1, c, Dh)

GQA: K/V carry their native Hkv heads; the index maps route grid row
b·H + h to kv row b·Hkv + h//G (G = H/Hkv), so grouped query heads share
one kv stream without any jnp.repeat materialization in HBM.

Causality: local scores use a (c, c) lower-triangular mask; global scores
mask slots whose owning block ≥ the current grid block (slot i belongs to
block i // r).

Backward (`blockwise_causal_attn_bwd`) — same per-query-block decomposition,
on the grid (B·Hkv, nb, G) with the GQA group axis innermost: the joint
softmax is RECOMPUTED from the forward's saved per-row residuals (row max
`m` and denominator — the flash-attention trick, no stored probabilities),
then the five blockwise matmuls produce dq, dk_loc/dv_loc and dk̄/dv̄.
dk_loc/dv_loc (shared by the G query heads of a group) and dk̄/dv̄ (shared
additionally across the nb query blocks) accumulate in fp32 VMEM scratch
across consecutive grid steps and are emitted on each accumulator's last
contributing step — the inner axes sweep every contributor of a kv row
consecutively, so no output block is ever revisited after a flush, and GQA
still never repeats K/V in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import BWD_VMEM_LIMIT_BYTES, dequant, rows

NEG_INF = -1e30


def start_block_rows(start_blocks, B: int):
    """(B,) per-row start blocks as a (B, 1, 1) int32 array: one (1, 1, 1)
    block per row, whose last two dims equal the array's (Mosaic's rule)."""
    return jnp.asarray(start_blocks, jnp.int32).reshape(B, 1, 1)


def start_block_spec(row):
    """SMEM block of row `row(*grid_idx)`'s start block (`start_block_rows`)."""
    return pl.BlockSpec((1, 1, 1), lambda *idx: (row(*idx), 0, 0),
                        memory_space=pltpu.SMEM)


def _joint_scores(q, kl, kbar, blk_cut, scale, r):
    """Masked fp32 scores of one query block: local (c, c) causal scores and
    global (c, M) scores over compressed slots of blocks < blk_cut."""
    c = q.shape[0]
    M = kbar.shape[0]
    s_loc = jax.lax.dot_general(
        q, kl, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # (c, c)
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    s_loc = jnp.where(ti >= si, s_loc, NEG_INF)

    s_glob = jax.lax.dot_general(
        q, kbar, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # (c, M)
    slot_blk = jax.lax.broadcasted_iota(jnp.int32, (c, M), 1) // r
    s_glob = jnp.where(slot_blk < blk_cut, s_glob, NEG_INF)
    return s_loc, s_glob


def _attend_block(q, kl, vl, kbar, vbar, n, scale, r):
    """One query block's joint-softmax attention: returns (out fp32, m,
    denom) — the single forward body shared by the plain, residual-emitting
    and quantized kernels, so grad-time primal and inference forward can
    never diverge."""
    s_loc, s_glob = _joint_scores(q, kl, kbar, n, scale, r)
    m = jnp.maximum(jnp.max(s_loc, -1, keepdims=True),
                    jnp.max(s_glob, -1, keepdims=True))
    p_loc = jnp.exp(s_loc - m)
    p_glob = jnp.exp(s_glob - m)
    denom = jnp.sum(p_loc, -1, keepdims=True) + jnp.sum(p_glob, -1,
                                                        keepdims=True)
    out = jax.lax.dot_general(
        (p_loc / denom).astype(vl.dtype), vl, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out += jax.lax.dot_general(
        (p_glob / denom).astype(vbar.dtype), vbar, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out, m, denom


def _prefix_kernel(q_ref, kl_ref, vl_ref, ck_ref, cv_ref, nb0_ref, out_ref, *,
                   scale: float, r: int):
    """Forward of one query block: the compressed operand is a FULL slot
    buffer (the sequence's own slots in training, the slot-resident cache
    in chunk prefill, or the gathered sequence-parallel prefix — pinned
    either way) and the visibility cut shifts by the row's start block nb0
    — grid block n of the chunk is absolute block nb0 + n, so it sees slots
    of blocks < nb0 + n. nb0 arrives as the row's (1, 1, 1) int32 block in
    SMEM. Training runs it at nb0 = 0, so the offset-free and offset forms
    are one kernel."""
    n = pl.program_id(1)
    nb0 = nb0_ref[0, 0, 0]
    out, _, _ = _attend_block(q_ref[0], kl_ref[0], vl_ref[0], ck_ref[0],
                              cv_ref[0], n + nb0, scale, r)
    out_ref[0] = out.astype(out_ref.dtype)


def _prefix_kernel_res(q_ref, kl_ref, vl_ref, ck_ref, cv_ref, nb0_ref,
                       out_ref, m_ref, denom_ref, *, scale: float, r: int):
    """`_prefix_kernel` that also emits the softmax residuals (per-row max
    and denominator, fp32, stored lane-dense as (1, c) rows) — what makes
    the forward trainable: the fused backward recomputes the joint
    probabilities from them."""
    n = pl.program_id(1)
    nb0 = nb0_ref[0, 0, 0]
    out, m, denom = _attend_block(q_ref[0], kl_ref[0], vl_ref[0], ck_ref[0],
                                  cv_ref[0], n + nb0, scale, r)
    out_ref[0] = out.astype(out_ref.dtype)
    m_ref[0] = m.reshape(1, -1)
    denom_ref[0] = denom.reshape(1, -1)


def _prefix_kernel_q(q_ref, kl_ref, vl_ref, ck_ref, cv_ref, cks_ref, cvs_ref,
                     nb0_ref, out_ref, *, scale: float, r: int):
    """Quantized-cache variant of `_prefix_kernel`: the pinned compressed
    operand arrives int8/fp8 with per-slot fp32 scales and is dequantized IN
    VMEM before the shared `_attend_block` body; the chunk's own local K/V
    are activations and stay full precision. fp32 compute throughout (the
    dequantized prefix is fp32, and lax.dot_general needs matching operand
    dtypes)."""
    n = pl.program_id(1)
    nb0 = nb0_ref[0, 0, 0]
    ck = dequant(ck_ref[0], cks_ref[0])
    cv = dequant(cv_ref[0], cvs_ref[0])
    out, _, _ = _attend_block(
        q_ref[0].astype(jnp.float32), kl_ref[0].astype(jnp.float32),
        vl_ref[0].astype(jnp.float32), ck, cv, n + nb0, scale, r)
    out_ref[0] = out.astype(out_ref.dtype)


def blockwise_causal_prefix_attn_q(
    q: jax.Array,        # (B, H, P, Dh) — one prefill chunk of queries
    k: jax.Array,        # (B, Hkv, P, Dh) — chunk keys (local, exact)
    v: jax.Array,
    comp_k: jax.Array,   # (B, Hkv, M, Dh) int8/fp8 page gather
    comp_v: jax.Array,
    comp_k_s: jax.Array,  # (B, Hkv, M) fp32 per-slot scales
    comp_v_s: jax.Array,
    start_blocks: jax.Array,   # (B,) int32 — per-row absolute start block
    *,
    block_size: int,
    block_slots: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Quantized-cache sibling of :func:`blockwise_causal_prefix_attn`: same
    grid and GQA routing, the pinned compressed operand stays in its storage
    dtype until the in-VMEM dequant. Forward-only — the paged cache is a
    serving structure, never differentiated through."""
    B, H, P, Dh = q.shape
    Hkv = k.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    c = block_size
    assert P % c == 0, (P, c)
    nb = P // c
    M = comp_k.shape[2]
    q3 = q.reshape(B * H, P, Dh)
    k3 = k.reshape(B * Hkv, P, Dh)
    v3 = v.reshape(B * Hkv, P, Dh)
    ck3 = comp_k.reshape(B * Hkv, M, Dh)
    cv3 = comp_v.reshape(B * Hkv, M, Dh)
    nb0 = start_block_rows(start_blocks, B)

    def kv_row(bh):
        return (bh // H) * Hkv + (bh % H) // G

    out = pl.pallas_call(
        functools.partial(_prefix_kernel_q, scale=scale, r=block_slots),
        grid=(B * H, nb),
        in_specs=[
            pl.BlockSpec((1, c, Dh), lambda bh, n: (bh, n, 0)),
            pl.BlockSpec((1, c, Dh), lambda bh, n: (kv_row(bh), n, 0)),
            pl.BlockSpec((1, c, Dh), lambda bh, n: (kv_row(bh), n, 0)),
            pl.BlockSpec((1, M, Dh), lambda bh, n: (kv_row(bh), 0, 0)),
            pl.BlockSpec((1, M, Dh), lambda bh, n: (kv_row(bh), 0, 0)),
            pl.BlockSpec((1, 1, M), lambda bh, n: (kv_row(bh), 0, 0)),
            pl.BlockSpec((1, 1, M), lambda bh, n: (kv_row(bh), 0, 0)),
            start_block_spec(lambda bh, n: bh // H),
        ],
        out_specs=pl.BlockSpec((1, c, Dh), lambda bh, n: (bh, n, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, P, Dh), q.dtype),
        interpret=interpret,
    )(q3, k3, v3, ck3, cv3, rows(comp_k_s, B * Hkv),
      rows(comp_v_s, B * Hkv), nb0)
    return out.reshape(B, H, P, Dh)


def blockwise_causal_prefix_attn(
    q: jax.Array,        # (B, H, P, Dh) — one prefill chunk of queries
    k: jax.Array,        # (B, Hkv, P, Dh) — chunk keys (local, exact)
    v: jax.Array,
    comp_k: jax.Array,   # (B, Hkv, M, Dh) — slot-resident compressed cache
    comp_v: jax.Array,   #                   (chunk's own blocks already folded)
    start_blocks: jax.Array,   # (B,) int32 — per-row absolute start block
    *,
    block_size: int,
    block_slots: int,
    scale: float,
    interpret: bool = False,
    return_residuals: bool = False,
):
    """Blockwise-causal attention for a query chunk at a nonzero per-row
    start offset, against a full compressed slot buffer (the slot-resident
    cache during chunked prefill, or the all-gathered prefix under sequence
    parallelism).

    Same grid/GQA routing as :func:`blockwise_causal_attn`, but the pinned
    compressed operand is the FULL (M_total, Dh) slot buffer and the
    causality cut is shifted per row by `start_blocks` (one int32 per row,
    read from SMEM). M_total = (max_seq/c)·r must fit in VMEM — the same
    compression budget the decode kernel already pins. With
    ``return_residuals=True`` also emits the joint softmax's per-row
    (m, denom), each (B, H, P) fp32 — the residuals
    :func:`blockwise_causal_attn_bwd` consumes (with the same
    `start_blocks`) to run the fused backward of this offset form.
    """
    B, H, P, Dh = q.shape
    Hkv = k.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    c = block_size
    assert P % c == 0, (P, c)
    nb = P // c
    M = comp_k.shape[2]
    q3 = q.reshape(B * H, P, Dh)
    k3 = k.reshape(B * Hkv, P, Dh)
    v3 = v.reshape(B * Hkv, P, Dh)
    ck3 = comp_k.reshape(B * Hkv, M, Dh)
    cv3 = comp_v.reshape(B * Hkv, M, Dh)
    nb0 = start_block_rows(start_blocks, B)

    def kv_row(bh):
        return (bh // H) * Hkv + (bh % H) // G

    in_specs = [
        pl.BlockSpec((1, c, Dh), lambda bh, n: (bh, n, 0)),
        pl.BlockSpec((1, c, Dh), lambda bh, n: (kv_row(bh), n, 0)),
        pl.BlockSpec((1, c, Dh), lambda bh, n: (kv_row(bh), n, 0)),
        pl.BlockSpec((1, M, Dh), lambda bh, n: (kv_row(bh), 0, 0)),
        pl.BlockSpec((1, M, Dh), lambda bh, n: (kv_row(bh), 0, 0)),
        start_block_spec(lambda bh, n: bh // H),
    ]
    out_spec = pl.BlockSpec((1, c, Dh), lambda bh, n: (bh, n, 0))
    out_shape = jax.ShapeDtypeStruct((B * H, P, Dh), q.dtype)
    if return_residuals:
        res_spec = pl.BlockSpec((1, 1, c), lambda bh, n: (bh, 0, n))
        res_shape = jax.ShapeDtypeStruct((B * H, 1, P), jnp.float32)
        out, m, denom = pl.pallas_call(
            functools.partial(_prefix_kernel_res, scale=scale, r=block_slots),
            grid=(B * H, nb),
            in_specs=in_specs,
            out_specs=[out_spec, res_spec, res_spec],
            out_shape=[out_shape, res_shape, res_shape],
            interpret=interpret,
        )(q3, k3, v3, ck3, cv3, nb0)
        return (out.reshape(B, H, P, Dh), m.reshape(B, H, P),
                denom.reshape(B, H, P))
    out = pl.pallas_call(
        functools.partial(_prefix_kernel, scale=scale, r=block_slots),
        grid=(B * H, nb),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(q3, k3, v3, ck3, cv3, nb0)
    return out.reshape(B, H, P, Dh)


def blockwise_causal_attn(
    q: jax.Array,       # (B, H, S, Dh)
    k: jax.Array,       # (B, Hkv, S, Dh) — native kv heads, H % Hkv == 0
    v: jax.Array,
    kbar: jax.Array,    # (B, Hkv, M, Dh)  compressed slots, M = (S/c)*r
    vbar: jax.Array,
    *,
    block_size: int,
    block_slots: int,
    scale: float,
    interpret: bool = False,
    return_residuals: bool = False,
):
    """Fused blockwise-causal attention forward: the prefix form at start
    block 0 with the sequence's own M = (S/c)·r slots as the buffer.

    With ``return_residuals=True`` also returns the joint softmax's per-row
    max `m` and denominator (each (B, H, S) fp32) — the residuals
    :func:`blockwise_causal_attn_bwd` recomputes the probabilities from.
    """
    B, S = q.shape[0], q.shape[2]
    assert kbar.shape[2] == (S // block_size) * block_slots, (
        kbar.shape, S, block_size, block_slots)
    return blockwise_causal_prefix_attn(
        q, k, v, kbar, vbar, jnp.zeros((B,), jnp.int32),
        block_size=block_size, block_slots=block_slots, scale=scale,
        interpret=interpret, return_residuals=return_residuals)


def _bwd_kernel(q_ref, kl_ref, vl_ref, kbar_ref, vbar_ref, m_ref, d_ref,
                do_ref, nb0_ref, dq_ref, dkl_ref, dvl_ref, dkb_ref, dvb_ref,
                dkl_acc, dvl_acc, dkb_acc, dvb_acc, *,
                scale: float, r: int, nb: int, G: int):
    """One grid step = one (kv head, query block, group member): recompute the
    joint probabilities from the saved (m, denom) residuals, then the five
    blockwise matmuls. Grid is (B·Hkv, nb, G) with the group axis INNERMOST,
    so every contributor to a kv-row accumulator runs on consecutive steps:
    dk_loc/dv_loc accumulate over the G group members of query block n, and
    dk̄/dv̄ over all nb·G steps of the kv row — fp32 scratch, emitted on each
    accumulator's last contributing step. nb0 shifts the visibility cut for
    the offset (prefix / sequence-parallel) form — zero in the offset-free
    training form; slots at or beyond the shifted cut recompute to P = 0 and
    contribute nothing, so the full-buffer accumulators stay exact."""
    n = pl.program_id(1)
    g = pl.program_id(2)
    nb0 = nb0_ref[0, 0, 0]

    @pl.when(jnp.logical_and(n == 0, g == 0))
    def _init_glob():
        dkb_acc[...] = jnp.zeros_like(dkb_acc)
        dvb_acc[...] = jnp.zeros_like(dvb_acc)

    @pl.when(g == 0)
    def _init_loc():
        dkl_acc[...] = jnp.zeros_like(dkl_acc)
        dvl_acc[...] = jnp.zeros_like(dvl_acc)

    q = q_ref[0]                                     # (c, Dh)
    kl = kl_ref[0]
    kbar = kbar_ref[0]                               # (M, Dh)
    vl32 = vl_ref[0].astype(jnp.float32)
    vbar32 = vbar_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)               # (c, Dh)
    m = m_ref[0].reshape(-1, 1)                      # (1, c) row → (c, 1)
    denom = d_ref[0].reshape(-1, 1)

    # native-dtype score recompute — bit-identical to the forward's scores,
    # so p = exp(s − m)/denom reproduces the forward's exact probabilities
    s_loc, s_glob = _joint_scores(q, kl, kbar, n + nb0, scale, r)
    q32 = q.astype(jnp.float32)
    kl32 = kl.astype(jnp.float32)
    kbar32 = kbar.astype(jnp.float32)
    p_loc = jnp.exp(s_loc - m) / denom               # (c, c) joint probs
    p_glob = jnp.exp(s_glob - m) / denom             # (c, M)

    # dv = Pᵀ·do (masked entries have P = 0, so they contribute nothing)
    dvl_acc[...] += jax.lax.dot_general(
        p_loc, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (c, Dh)
    dvb_acc[...] += jax.lax.dot_general(
        p_glob, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (M, Dh)

    # dS = P ∘ (dP − rowsum(dP ∘ P)) over the JOINT row
    dp_loc = jax.lax.dot_general(
        do, vl32, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (c, c)
    dp_glob = jax.lax.dot_general(
        do, vbar32, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (c, M)
    delta = (jnp.sum(dp_loc * p_loc, -1, keepdims=True)
             + jnp.sum(dp_glob * p_glob, -1, keepdims=True))
    ds_loc = p_loc * (dp_loc - delta)
    ds_glob = p_glob * (dp_glob - delta)

    dq = jax.lax.dot_general(
        ds_loc, kl32, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dq += jax.lax.dot_general(
        ds_glob, kbar32, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    dkl_acc[...] += jax.lax.dot_general(
        ds_loc, q32, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (c, Dh)
    dkb_acc[...] += jax.lax.dot_general(
        ds_glob, q32, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (M, Dh)

    @pl.when(g == G - 1)
    def _emit_loc():
        dkl_ref[0] = dkl_acc[...]
        dvl_ref[0] = dvl_acc[...]

    @pl.when(jnp.logical_and(n == nb - 1, g == G - 1))
    def _emit_glob():
        dkb_ref[0] = dkb_acc[...]
        dvb_ref[0] = dvb_acc[...]


def blockwise_causal_attn_bwd(
    q: jax.Array,       # (B, H, S, Dh)
    k: jax.Array,       # (B, Hkv, S, Dh) — native kv heads
    v: jax.Array,
    kbar: jax.Array,    # (B, Hkv, M, Dh)  compressed slots, M = (S/c)*r
    vbar: jax.Array,
    m: jax.Array,       # (B, H, S) fp32 — forward's joint-softmax row max
    denom: jax.Array,   # (B, H, S) fp32 — forward's joint-softmax denominator
    do: jax.Array,      # (B, H, S, Dh) — output cotangent
    *,
    block_size: int,
    block_slots: int,
    scale: float,
    interpret: bool = False,
    start_blocks: jax.Array = None,   # (B,) int32 — offset (prefix) form
):
    """Fused Pallas backward of :func:`blockwise_causal_attn` — and, with
    `start_blocks`, of :func:`blockwise_causal_prefix_attn`.

    Returns ``(dq, dk_loc, dv_loc, dkbar, dvbar)`` — dq in q's dtype,
    everything else fp32 (the accumulation dtype): dk_loc/dv_loc are the
    gradients through the LOCAL (own-block, exact) attention; dk̄/dv̄ are the
    compressed-slot gradients the caller chains through the linear
    `compress_blocks` VJP to reach dk/dv/dE/dF. No (S × nb·r) global score
    tensor ever hits HBM — scores live one query block at a time, exactly
    like the forward.

    With ``start_blocks`` (the offset form) the query chunk starts at
    per-row absolute block nb0[b] and kbar/vbar are a FULL slot buffer
    (M ≥ (nb0 + S/c)·r): dk̄/dv̄ cover the whole buffer, with exact zeros on
    slots this chunk's queries never see — under sequence parallelism those
    partial buffers are what the all-gather transpose psum-reduces across
    shards.
    """
    B, H, S, Dh = q.shape
    Hkv = k.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    c = block_size
    assert S % c == 0
    nb = S // c
    M = kbar.shape[2]
    if start_blocks is None:
        assert M == nb * block_slots, (M, nb, block_slots)
        start_blocks = jnp.zeros((B,), jnp.int32)
    nb0 = start_block_rows(start_blocks, B)
    q3 = q.reshape(B * H, S, Dh)
    k3 = k.reshape(B * Hkv, S, Dh)
    v3 = v.reshape(B * Hkv, S, Dh)
    kb3 = kbar.reshape(B * Hkv, M, Dh)
    vb3 = vbar.reshape(B * Hkv, M, Dh)
    m3 = m.reshape(B * H, 1, S)
    d3 = denom.reshape(B * H, 1, S)
    do3 = do.reshape(B * H, S, Dh)

    # kv row bkv, group member g ↔ query row (bkv//Hkv)·H + (bkv%Hkv)·G + g —
    # the forward's kv_row routing inverted (per-step index math, no HBM
    # repeat of K/V or the compressed slots).
    def q_row(bkv, g):
        return (bkv // Hkv) * H + (bkv % Hkv) * G + g

    q_blk = pl.BlockSpec((1, c, Dh), lambda bkv, n, g: (q_row(bkv, g), n, 0))
    kv_blk = pl.BlockSpec((1, c, Dh), lambda bkv, n, g: (bkv, n, 0))
    slot_blk = pl.BlockSpec((1, M, Dh), lambda bkv, n, g: (bkv, 0, 0))
    res_blk = pl.BlockSpec((1, 1, c), lambda bkv, n, g: (q_row(bkv, g), 0, n))
    dq, dkl, dvl, dkb, dvb = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, r=block_slots, nb=nb,
                          G=G),
        grid=(B * Hkv, nb, G),
        in_specs=[q_blk, kv_blk, kv_blk, slot_blk, slot_blk, res_blk, res_blk,
                  q_blk, start_block_spec(lambda bkv, n, g: bkv // Hkv)],
        out_specs=[q_blk, kv_blk, kv_blk, slot_blk, slot_blk],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
            jax.ShapeDtypeStruct((B * Hkv, S, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, S, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, M, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, M, Dh), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((c, Dh), jnp.float32),
            pltpu.VMEM((c, Dh), jnp.float32),
            pltpu.VMEM((M, Dh), jnp.float32),
            pltpu.VMEM((M, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=BWD_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(q3, k3, v3, kb3, vb3, m3, d3, do3, nb0)
    return (dq.reshape(B, H, S, Dh), dkl.reshape(B, Hkv, S, Dh),
            dvl.reshape(B, Hkv, S, Dh), dkb.reshape(B, Hkv, M, Dh),
            dvb.reshape(B, Hkv, M, Dh))
