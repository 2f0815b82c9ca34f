"""Sequence-parallel Linformer attention (beyond-paper; DESIGN.md §3).

Because the paper's compression K̄ = EᵀK is a LINEAR reduction over the
sequence axis, sharding the sequence across devices costs only a collective
over the (k × d) compressed operands — communication independent of n.
Standard attention under sequence parallelism must ring-exchange O(n·d) of
K/V (ring attention); Linformer needs O(k·d).

Two forms, both exposed as SHARD-LOCAL bodies consumed inside the manual
region that `parallel/plan.py` opens (the plan owns the shard_map specs;
these functions own the per-shard math + collectives):

* :func:`sp_exact_linformer_attention` — the exact (bidirectional) form:
  each device projects its sequence shard with its E/F row block, psums the
  tiny compressed K̄/V̄, then attends its local queries. One psum of
  2·(B, K, Hkv, Dh) bytes.

* :func:`sp_blockwise_causal_attention` — the causal (blockwise) form: each
  device compresses its LOCAL blocks into r slots each, all-gathers the
  compressed prefix (2·(B, (S/c)·r, Hkv, Dh) bytes — the Linformer win: the
  raw causal blocks stay RESIDENT, only the c/r-compressed slots move), and
  attends its local query blocks through the offset (prefix-form) kernel at
  this device's absolute block offset. Training works end to end: the fused
  backward's full-buffer fp32 dk̄/dv̄ accumulators are reduced across shards
  by the all-gather's transpose (a psum-scatter inside the manual region),
  then chained through the local `compress_blocks` VJP.

`seq_parallel_linformer_attention` is the self-contained exact-form
shard_map kept for direct use and the test_distributed parity oracle.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import causal as causal_lib
from repro.core import linformer as lin_lib
from repro.parallel.sharding import ParallelCtx


# ---------------------------------------------------------------------------
# Shard-local bodies (run inside the plan's manual region)
# ---------------------------------------------------------------------------


def sp_exact_linformer_attention(
    q_l: jax.Array,          # (B, S/sp, H_l, Dh) — this shard's queries
    k_l: jax.Array,          # (B, S/sp, Hkv_l, Dh)
    v_l: jax.Array,
    E_l: jax.Array,          # (S/sp, K) — this shard's E row block
    F_l: jax.Array,
    *,
    seq_axis: str,
    scale: float,
    fused: bool,
) -> jax.Array:
    """Exact-form shard-local body: partial projection over local sequence
    rows, psum of the compressed K̄/V̄, local-query attention. Output stays
    sequence-sharded with zero further communication."""
    if fused:
        from repro.kernels import ops as kernel_ops
        kbar = kernel_ops.fused_seq_projection(k_l, E_l)
        vbar = kernel_ops.fused_seq_projection(v_l, F_l)
    else:
        kbar = jnp.einsum("bshd,sk->bkhd", k_l, E_l.astype(k_l.dtype))
        vbar = jnp.einsum("bshd,sk->bkhd", v_l, F_l.astype(v_l.dtype))
    kbar = jax.lax.psum(kbar, seq_axis)       # (B, K, Hkv, Dh) — tiny
    vbar = jax.lax.psum(vbar, seq_axis)
    if fused:
        return kernel_ops.fused_linformer_attention(q_l, kbar, vbar,
                                                    scale=scale)
    return lin_lib.attend_compressed(q_l, kbar, vbar, scale=scale)


def sp_blockwise_causal_attention(
    q_l: jax.Array,          # (B, S/sp, H_l, Dh) — this shard's queries
    k_l: jax.Array,          # (B, S/sp, Hkv_l, Dh) — resident causal blocks
    v_l: jax.Array,
    E_l: jax.Array,          # (c, r) or (Hkv_l, c, r)
    F_l: jax.Array,
    *,
    seq_axis: str,
    block_size: int,
    block_slots: int,
    scale: float,
    fused: bool,
    backward_impl: str = "fused",
) -> jax.Array:
    """Blockwise-causal shard-local body: compress local blocks, all-gather
    the compressed prefix, attend local queries at this shard's block offset.

    The sequence axis must be sharded CONTIGUOUSLY (shard_map's convention),
    with the local length a multiple of `block_size`: shard d then holds
    absolute blocks [d·nb_l, (d+1)·nb_l). `tiled=True` all-gather
    concatenates shards in axis order, so gathered slot m belongs to
    absolute block m // r — exactly the visibility rule the prefix kernel's
    causality cut applies at start block d·nb_l. Under `jax.grad`, the
    all-gather transposes to a psum-scatter: every shard's full-buffer
    dk̄/dv̄ (fused backward accumulators, exact zeros on slots its queries
    never see) are summed and re-sharded before the local
    `compress_blocks` VJP chains them into dk/dv/dE/dF.
    """
    B, S_l, Hkv, Dh = k_l.shape
    c, r = block_size, block_slots
    if S_l % c != 0:
        raise ValueError(
            f"sequence-parallel shard length {S_l} is not a multiple of the "
            f"attention block size {c}")
    nb_l = S_l // c
    kbar_l = causal_lib.compress_blocks(
        k_l.reshape(B, nb_l, c, Hkv, Dh), E_l).reshape(B, nb_l * r, Hkv, Dh)
    vbar_l = causal_lib.compress_blocks(
        v_l.reshape(B, nb_l, c, Hkv, Dh), F_l).reshape(B, nb_l * r, Hkv, Dh)
    kbar = jax.lax.all_gather(kbar_l, seq_axis, axis=1, tiled=True)
    vbar = jax.lax.all_gather(vbar_l, seq_axis, axis=1, tiled=True)
    start = jax.lax.axis_index(seq_axis) * nb_l
    start_blocks = jnp.broadcast_to(start, (B,))
    if fused:
        from repro.kernels import ops as kernel_ops
        return kernel_ops.fused_chunk_prefill_attention(
            q_l, k_l, v_l, kbar, vbar, start_blocks, block_size=c,
            block_slots=r, scale=scale, backward_impl=backward_impl)
    return causal_lib.blockwise_causal_prefix_attention(
        q_l, k_l, v_l, kbar, vbar, start_blocks, block_size=c,
        block_slots=r, scale=scale)


# ---------------------------------------------------------------------------
# Self-contained exact-form shard_map (kept: direct use + parity oracle)
# ---------------------------------------------------------------------------


def seq_parallel_linformer_attention(
    q: jax.Array,            # (B, S, H, Dh)
    k: jax.Array,            # (B, S, Hkv, Dh)
    v: jax.Array,
    E: jax.Array,            # (S, K) — row-sharded with the sequence
    F: jax.Array,
    ctx: ParallelCtx,
    *,
    seq_axis: Optional[str] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact Linformer attention with the sequence axis sharded over
    `seq_axis` (default: the model axis). Returns (B, S, H, Dh) sharded the
    same way. Communication: one psum of 2·(B, K, Hkv, Dh)."""
    axis = seq_axis or ctx.model_axis
    mesh = ctx.mesh
    assert mesh is not None
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5

    def body(q_l, k_l, v_l, E_l, F_l):
        return sp_exact_linformer_attention(
            q_l, k_l, v_l, E_l, F_l, seq_axis=axis, scale=scale_,
            fused=False)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis, None, None), P(None, axis, None, None),
                  P(None, axis, None, None), P(axis, None), P(axis, None)),
        out_specs=P(None, axis, None, None),
        check_vma=False,
    )(q, k, v, E, F)


# ---------------------------------------------------------------------------
# Communication-cost model (docs/parallelism.md §Comm bytes)
# ---------------------------------------------------------------------------


def seq_parallel_comm_bytes(n: int, k: int, d_total: int, shards: int,
                            dtype_bytes: int = 2) -> Tuple[int, int]:
    """(linformer_bytes, ring_attention_bytes) per device for one layer of
    the EXACT form — the collective-cost comparison quoted in
    EXPERIMENTS.md §Perf: a psum of K̄/V̄ vs a ring exchange of raw K/V."""
    lin = 2 * k * d_total * dtype_bytes                   # psum of K̄,V̄
    ring = 2 * (n // shards) * d_total * (shards - 1) * dtype_bytes
    return lin, ring


def blockwise_sp_comm_bytes(n: int, block_size: int, block_slots: int,
                            d_total: int, shards: int,
                            dtype_bytes: int = 2) -> Tuple[int, int]:
    """(linformer_bytes, ring_attention_bytes) per device for one layer of
    the CAUSAL (blockwise) form under sequence parallelism: the all-gather
    moves only the compressed prefix — 2·(n/c)·r·d bytes, a c/r-fold
    reduction over ring-exchanging the raw K/V — while the local causal
    blocks never leave their shard."""
    m_total = (n // block_size) * block_slots
    lin = 2 * m_total * d_total * dtype_bytes             # all-gather of k̄,v̄
    ring = 2 * (n // shards) * d_total * (shards - 1) * dtype_bytes
    return lin, ring
