"""Fused Linformer attention Pallas kernel (TPU target).

Computes out = softmax(Q·K̄ᵀ/√d) · V̄ with K̄,V̄ the sequence-compressed
(k × Dh) keys/values.

TPU adaptation (DESIGN.md §3): because k ≤ 512, the ENTIRE compressed K̄/V̄
per head fits in VMEM (512×128 bf16 = 128 KiB), so the kernel pins them and
streams Q blocks — exact one-pass softmax with no flash-style online
renormalization. Score matmuls are (bq × Dh)·(Dh × k) and (bq × k)·(k × Dh):
both MXU-aligned when bq, Dh, k are multiples of 128 (the paper's k = 128/256
already are).

Grid: (B·H, S / bq). Block shapes:
  q    (1, bq, Dh)   — streamed per grid step
  k̄,v̄  (1, k,  Dh)   — pinned (same block for every s-step)
  out  (1, bq, Dh)

`decode_attn` is the single-token decode variant used by the
continuous-batching decode path: the raw ring-buffer block and the
compressed prefix slots stay TWO pinned operands (no per-step HBM
concatenate — the cache-residency contract), each with a per-row (B, ·)
additive validity bias (0 for attendable slots, NEG_INF otherwise — every
row sits at its own position); the softmax normalizes over their
concatenated scores inside the kernel.

The multi-token sibling — a prefill CHUNK at a nonzero per-row start
offset against the same slot-resident compressed cache (the serving
scheduler's chunked-admission path) — is
blockwise_causal_attn.blockwise_causal_prefix_attn, wrapped by
ops.fused_chunk_prefill_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import dequant, rows


def _softmax_attend(q, kbar, vbar, scale):
    s = jax.lax.dot_general(
        q, kbar, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # (bq, k)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jax.lax.dot_general(
        p.astype(vbar.dtype), vbar, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _kernel(q_ref, kbar_ref, vbar_ref, out_ref, *, scale: float):
    out = _softmax_attend(q_ref[0], kbar_ref[0], vbar_ref[0], scale)
    out_ref[0] = out.astype(out_ref.dtype)


def linformer_attn(
    q: jax.Array,       # (B, H, S, Dh)
    kbar: jax.Array,    # (B, H, K, Dh)
    vbar: jax.Array,    # (B, H, K, Dh)
    *,
    scale: float,
    block_q: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, Dh = q.shape
    K = kbar.shape[2]
    bq = min(block_q, S)
    assert S % bq == 0, (S, bq)
    q3 = q.reshape(B * H, S, Dh)
    k3 = kbar.reshape(B * H, K, Dh)
    v3 = vbar.reshape(B * H, K, Dh)

    grid = (B * H, S // bq)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, K, Dh), lambda bh, s: (bh, 0, 0)),
            pl.BlockSpec((1, K, Dh), lambda bh, s: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda bh, s: (bh, s, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(B, H, S, Dh)


# ---------------------------------------------------------------------------
# Single-token decode kernel: [raw block | compressed prefix] as two pinned
# operands (cache residency — no per-step HBM concatenate)
# ---------------------------------------------------------------------------


def _attend_pinned(q, rk, rv, ck, cv, bl, bg, scale):
    """Array-level decode attend over the two pinned operands: one-pass
    softmax across the concatenated [raw block | compressed prefix] scores.
    Shared by the dense and the dequant-in-kernel quantized variants."""
    s_loc = jax.lax.dot_general(
        q, rk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale + bl
    s_glob = jax.lax.dot_general(
        q, ck, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale + bg
    s = jnp.concatenate([s_loc, s_glob], axis=-1)            # (G, c + M)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    c = rk.shape[0]
    out = jax.lax.dot_general(
        p[:, :c].astype(rv.dtype), rv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out += jax.lax.dot_general(
        p[:, c:].astype(cv.dtype), cv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out


def _decode_kernel(q_ref, rk_ref, rv_ref, ck_ref, cv_ref, bl_ref, bg_ref,
                   out_ref, *, scale: float):
    out = _attend_pinned(q_ref[0], rk_ref[0], rv_ref[0], ck_ref[0],
                         cv_ref[0], bl_ref[0], bg_ref[0], scale)
    out_ref[0] = out.astype(out_ref.dtype)


def _decode_kernel_q(q_ref, rk_ref, rv_ref, ck_ref, cv_ref,
                     rks_ref, rvs_ref, cks_ref, cvs_ref,
                     bl_ref, bg_ref, out_ref, *, scale: float):
    """Quantized-cache decode kernel: operands arrive int8/fp8 with per-token
    (ring) / per-slot (pages) fp32 scales and are dequantized IN VMEM —
    HBM traffic for the two pinned caches shrinks with the storage dtype."""
    rk = dequant(rk_ref[0], rks_ref[0])
    rv = dequant(rv_ref[0], rvs_ref[0])
    ck = dequant(ck_ref[0], cks_ref[0])
    cv = dequant(cv_ref[0], cvs_ref[0])
    out = _attend_pinned(q_ref[0].astype(jnp.float32), rk, rv, ck, cv,
                         bl_ref[0], bg_ref[0], scale)
    out_ref[0] = out.astype(out_ref.dtype)


def decode_attn(
    q: jax.Array,        # (B, Hkv, G, Dh) — GQA group folded into the q axis
    raw_k: jax.Array,    # (B, Hkv, c, Dh) — raw ring buffer, pinned
    raw_v: jax.Array,
    comp_k: jax.Array,   # (B, Hkv, M, Dh) — compressed slots, pinned
    comp_v: jax.Array,
    bias_loc: jax.Array,   # (B, c) fp32: 0 attendable / NEG_INF masked
    bias_glob: jax.Array,  # (B, M) fp32
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, G, Dh = q.shape
    c, M = raw_k.shape[2], comp_k.shape[2]
    grid = (B * Hkv,)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, c, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, c, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, M, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, M, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda bh: (bh // Hkv, 0, 0)),
            pl.BlockSpec((1, 1, M), lambda bh: (bh // Hkv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dh), lambda bh: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Dh), q.dtype),
        interpret=interpret,
    )(q.reshape(B * Hkv, G, Dh), raw_k.reshape(B * Hkv, c, Dh),
      raw_v.reshape(B * Hkv, c, Dh), comp_k.reshape(B * Hkv, M, Dh),
      comp_v.reshape(B * Hkv, M, Dh), rows(bias_loc, B), rows(bias_glob, B))
    return out.reshape(B, Hkv, G, Dh)


def decode_attn_q(
    q: jax.Array,        # (B, Hkv, G, Dh) — GQA group folded into the q axis
    raw_k: jax.Array,    # (B, Hkv, c, Dh) int8/fp8 ring, pinned
    raw_v: jax.Array,
    comp_k: jax.Array,   # (B, Hkv, M, Dh) int8/fp8 page gather, pinned
    comp_v: jax.Array,
    raw_k_s: jax.Array,  # (B, Hkv, c) fp32 per-token scales
    raw_v_s: jax.Array,
    comp_k_s: jax.Array,  # (B, Hkv, M) fp32 per-slot scales
    comp_v_s: jax.Array,
    bias_loc: jax.Array,   # (B, c) fp32: 0 attendable / NEG_INF masked
    bias_glob: jax.Array,  # (B, M) fp32
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Quantized-cache sibling of :func:`decode_attn`: identical grid and
    pinning, four extra per-(row, head) scale operands, dequantization
    in-kernel (VMEM) — HBM traffic for the two pinned caches shrinks with
    the storage dtype. Forward-only: serving decode never differentiates
    through the cache."""
    B, Hkv, G, Dh = q.shape
    c, M = raw_k.shape[2], comp_k.shape[2]
    grid = (B * Hkv,)
    kv3 = lambda x, n: x.reshape(B * Hkv, n, Dh)
    out = pl.pallas_call(
        functools.partial(_decode_kernel_q, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, c, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, c, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, M, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, M, Dh), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, 1, M), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, 1, M), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda bh: (bh // Hkv, 0, 0)),
            pl.BlockSpec((1, 1, M), lambda bh: (bh // Hkv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dh), lambda bh: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Dh), q.dtype),
        interpret=interpret,
    )(q.reshape(B * Hkv, G, Dh), kv3(raw_k, c), kv3(raw_v, c),
      kv3(comp_k, M), kv3(comp_v, M), rows(raw_k_s, B * Hkv),
      rows(raw_v_s, B * Hkv), rows(comp_k_s, B * Hkv),
      rows(comp_v_s, B * Hkv), rows(bias_loc, B), rows(bias_glob, B))
    return out.reshape(B, Hkv, G, Dh)
