"""Decode-time caches.

Two cache families:

* :func:`init_compressed_cache` — the Linformer-causal cache. Per layer it
  holds (a) a raw ring buffer for the current (incomplete) block of K/V and
  (b) a compressed slot buffer: r slots per completed block. Total width for a
  context of length n is c + r·⌊n/c⌋ — e.g. 32k context @ c=256, r=16 becomes
  2304 slots vs 32768 (14× smaller); 512k context becomes 33k slots (16×).

* :func:`init_full_cache` — the standard-attention baseline: full (S, Hkv, Dh)
  K/V per layer.

Caches are plain dicts of arrays (pytrees) with the layer axis leading. The
per-layer step functions below read one layer's view (the leaf without its
layer axis) and return ``(out, writes)``: a :class:`SlotWrite` per leaf they
change, naming only the slots that change (a decode step's token, a completed
block's r slots; a prefill chunk's slots). :func:`write_cache` applies them —
to the stacked cache at a layer index (models/transformer.py carries the stack
through its layer scan, so a donated pool is updated in place and nothing
rewrites a whole layer buffer), or to a lone layer view.

Per-row positions: the cache carries a ``lengths`` (B,) int32 vector — one
position counter per batch row — instead of a shared scalar. Every row of a
decode batch may sit at its own position (the continuous-batching scheduler
admits/evicts rows between decode chunks, so rows are never aligned); masks,
ring-buffer writes and the block fold are all per-row. The decode attention
functions still accept a scalar ``t`` (broadcast to every row), which is the
legacy shared-position behaviour.

Chunked prefill: :func:`compressed_prefill_chunk` / :func:`full_prefill_chunk`
are the multi-token siblings of the decode steps — they commit one P-token
prefill chunk per row at the row's own offset (mid-prefill cache writes at
arbitrary per-row positions; for the compressed cache every chunk boundary
is a block-fold boundary, so chunks fold straight into compressed slots).
The serving scheduler uses them to stream long prompts into pool slots
between decode chunks.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.causal import NEG_INF


def rowwise_t(t: jax.Array, batch: int) -> jax.Array:
    """Broadcast a scalar position to a (B,) per-row position vector."""
    t = jnp.asarray(t, jnp.int32)
    if t.ndim == 0:
        return jnp.broadcast_to(t, (batch,))
    return t


class SlotWrite(NamedTuple):
    """The slots one step writes into one cache leaf. Update u puts
    ``value[u]`` (n, *rest) at ``[a0[u], a1[u]:a1[u] + n]`` of the leaf's
    per-layer buffer (A0, A1, *rest): a0 is the batch row (the arena page
    for page leaves), a1 the first slot. The slot start clamps to A1 - n, as
    dynamic_update_slice's does; updates apply in order. Where ``commit[u]``
    is false, update u writes back the slots' current contents instead."""
    a0: jax.Array             # (U,) int32
    a1: jax.Array             # (U,) int32
    value: jax.Array          # (U, n, *rest)
    commit: Optional[jax.Array] = None   # (U,) bool; None commits all


def row_write(start: jax.Array, value: jax.Array,
              commit: Optional[jax.Array] = None) -> SlotWrite:
    """Row b writes value[b] (n, *rest) at slot start[b]."""
    return SlotWrite(jnp.arange(value.shape[0], dtype=jnp.int32), start,
                     value, commit)


def write_slots(buf: jax.Array, w: SlotWrite, *lead) -> jax.Array:
    """``buf`` (*L, A0, A1, *rest) with ``w``'s updates written at leading
    index ``lead`` (the layer of a stacked leaf; none for a layer view): one
    dynamic_update_slice per update, after a dynamic_slice of the same
    window where ``w`` has a commit mask. A buffer dead afterwards (a donated
    pool, a scan carry) is updated in place, and only the written slots
    move."""
    value = w.value.astype(buf.dtype)
    zeros = (0,) * (value.ndim - 2)
    for u in range(value.shape[0]):
        start = (*lead, w.a0[u], w.a1[u], *zeros)
        upd = value[u].reshape((1,) * (len(lead) + 1) + value.shape[1:])
        if w.commit is not None:
            cur = jax.lax.dynamic_slice(buf, start, upd.shape)
            upd = jnp.where(w.commit[u], upd, cur)
        buf = jax.lax.dynamic_update_slice(buf, upd, start)
    return buf


def write_cache(cache: Dict[str, jax.Array], writes: Dict[str, SlotWrite],
                *lead) -> Dict[str, jax.Array]:
    """Apply a step's ``writes`` to the leaves of ``cache`` they name (see
    :func:`write_slots`); every other leaf passes through untouched."""
    return {k: write_slots(v, writes[k], *lead) if k in writes else v
            for k, v in cache.items()}


# ---------------------------------------------------------------------------
# Compressed (Linformer-causal) cache
# ---------------------------------------------------------------------------


def compressed_cache_spec(
    *, num_layers: int, batch: int, max_seq: int, block_size: int,
    block_slots: int, num_kv_heads: int, head_dim: int, dtype=jnp.bfloat16,
) -> Dict[str, jax.ShapeDtypeStruct]:
    max_blocks = max_seq // block_size
    M = max_blocks * block_slots
    kv = lambda *s: jax.ShapeDtypeStruct(s, dtype)
    return {
        "raw_k": kv(num_layers, batch, block_size, num_kv_heads, head_dim),
        "raw_v": kv(num_layers, batch, block_size, num_kv_heads, head_dim),
        "comp_k": kv(num_layers, batch, M, num_kv_heads, head_dim),
        "comp_v": kv(num_layers, batch, M, num_kv_heads, head_dim),
        "lengths": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def init_compressed_cache(**kw) -> Dict[str, jax.Array]:
    spec = compressed_cache_spec(**kw)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in spec.items()}


def compressed_decode_attention(
    q_t: jax.Array,           # (B, 1, H, Dh) — rope already applied at pos t
    k_t: jax.Array,           # (B, 1, Hkv, Dh)
    v_t: jax.Array,
    layer_cache: Dict[str, jax.Array],   # layer view: raw_k (B,c,Hkv,Dh), comp_k (B,M,Hkv,Dh)
    E: jax.Array,             # (c, r) or (Hkv, c, r)
    F: jax.Array,
    t: jax.Array,             # () or (B,) int32 — tokens already cached per row
    *,
    scale: Optional[float] = None,
    plan=None,                # AttentionPlan | backend string | None
) -> Tuple[jax.Array, Dict[str, SlotWrite]]:
    """One decode step of blockwise-causal Linformer attention.

    Appends (k_t, v_t) at each row's position t[b], attends [raw block ≤ t[b]
    | compressed prefix blocks], and folds a row's block into r compressed
    slots when t[b] completes it. Every mask, ring-buffer write and block
    fold is PER ROW — rows of a continuous batch sit at unequal positions.
    A scalar t broadcasts to all rows (the legacy shared-position form).

    Returns (out (B,1,H,Dh), writes): per row, the ring token at t mod c
    (``raw_k``/``raw_v``) and the r slots at (t // c)·r (``comp_k``/
    ``comp_v``) — the fold where t completes the block, else the slots as
    they were — to be applied with :func:`write_cache`.

    The attention math itself dispatches through `plan`
    (parallel/plan.py AttentionPlan; a bare backend string resolves to a
    single-device plan): the fused plan routes through the Pallas decode
    kernel — GQA group axis folded into the kernel's query axis, raw +
    compressed caches as two pinned operands (per-shard slots under tensor
    parallelism), slot validity as per-row additive score biases. Cache
    bookkeeping here is identical for every plan.
    """
    from repro.parallel.plan import as_plan
    plan = as_plan(plan)
    raw_k, raw_v = layer_cache["raw_k"], layer_cache["raw_v"]
    comp_k, comp_v = layer_cache["comp_k"], layer_cache["comp_v"]
    B, c, Hkv, Dh = raw_k.shape
    M = comp_k.shape[1]
    r = E.shape[-1]
    scale_ = scale if scale is not None else Dh ** -0.5

    t = rowwise_t(t, B)
    pos = jnp.mod(t, c)                         # (B,)
    blk = t // c                                # (B,)

    writes = {"raw_k": row_write(pos, k_t.astype(raw_k.dtype)),
              "raw_v": row_write(pos, v_t.astype(raw_v.dtype))}
    raw_k = write_slots(raw_k, writes["raw_k"])
    raw_v = write_slots(raw_v, writes["raw_v"])

    loc_ok = jnp.arange(c)[None, :] <= pos[:, None]         # (B, c)
    glob_ok = jnp.arange(M)[None, :] < (blk * r)[:, None]   # (B, M)
    out = plan.decode_attention(q_t, raw_k, raw_v, comp_k, comp_v,
                                loc_ok, glob_ok, scale=scale_)

    # fold a row's block into its compressed slots when it completes
    # (pos[b] == c-1): computed for every row, committed per row by a select
    # at slot size between the fold and the slots' current contents, read
    # where they are written (write_slots).
    if E.ndim == 2:
        new_ks = jnp.einsum("bchd,cr->brhd", raw_k, E.astype(raw_k.dtype))
        new_vs = jnp.einsum("bchd,cr->brhd", raw_v, F.astype(raw_v.dtype))
    else:
        new_ks = jnp.einsum("bchd,hcr->brhd", raw_k, E.astype(raw_k.dtype))
        new_vs = jnp.einsum("bchd,hcr->brhd", raw_v, F.astype(raw_v.dtype))
    done = pos == (c - 1)
    writes["comp_k"] = row_write(blk * r, new_ks, done)
    writes["comp_v"] = row_write(blk * r, new_vs, done)
    return out, writes


def compressed_prefill_chunk(
    q: jax.Array,             # (B, P, H, Dh) — one prefill chunk, rope applied
    k: jax.Array,             # (B, P, Hkv, Dh)
    v: jax.Array,
    layer_cache: Dict[str, jax.Array],
    E: jax.Array,             # (c, r) or (Hkv, c, r)
    F: jax.Array,
    t0: jax.Array,            # (B,) int32 — row's current length, multiple of c
    *,
    scale: Optional[float] = None,
    plan=None,                # AttentionPlan | backend string | None
) -> Tuple[jax.Array, Dict[str, SlotWrite]]:
    """One chunked-prefill step of blockwise-causal Linformer attention.

    Mid-prefill cache write at an arbitrary PER-ROW offset: row b's chunk
    covers absolute positions [t0[b], t0[b] + P); every chunk boundary is a
    block-fold boundary (t0 and P are multiples of c), so the chunk's P/c
    blocks fold straight into r compressed slots each, written at slot offset
    (t0[b] // c)·r — the raw ring buffer is untouched (it only ever holds the
    current incomplete block, and a chunk never ends mid-block; remainder
    tokens go through the decode path). Attention then reads the UPDATED slot
    buffer: [own block, causal | compressed slots of absolute blocks
    < t0//c + j] — identical math to the monolithic prefill forward when the
    cache dtype matches the activation dtype. With a lower-precision cache
    (e.g. bf16 under fp32 compute) earlier chunks' slots are read back
    cache-rounded, where the monolithic forward attends them at full
    precision and only rounds when materializing the cache — the standard
    chunked-prefill tradeoff.

    Rows whose chunk is partially padded (n_valid < P, whole padded blocks at
    the END) write garbage slots beyond their valid blocks; those slots are
    never visible (visibility is bounded by the row's committed length) and
    are overwritten by the next chunk or by the decode-time block fold before
    visibility reaches them, so no masking of the write is needed.

    Returns (out (B, P, H, Dh), writes): each row's P/c·r slots at
    (t0[b] // c)·r in ``comp_k``/``comp_v`` (see :func:`write_cache`).
    """
    from repro.parallel.plan import as_plan
    plan = as_plan(plan)
    comp_k, comp_v = layer_cache["comp_k"], layer_cache["comp_v"]
    B, P, Hkv, Dh = k.shape
    c = layer_cache["raw_k"].shape[1]
    r = E.shape[-1]
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    if P % c != 0:
        raise ValueError(f"prefill chunk P={P} not a multiple of block {c}")
    nb = P // c

    from repro.core.causal import compress_blocks
    kbar = compress_blocks(k.reshape(B, nb, c, Hkv, Dh), E)
    vbar = compress_blocks(v.reshape(B, nb, c, Hkv, Dh), F)
    t0 = rowwise_t(t0, B)
    slot0 = (t0 // c) * r
    writes = {
        "comp_k": row_write(slot0, kbar.reshape(B, nb * r, Hkv, Dh)
                            .astype(comp_k.dtype)),
        "comp_v": row_write(slot0, vbar.reshape(B, nb * r, Hkv, Dh)
                            .astype(comp_v.dtype))}
    comp_k = write_slots(comp_k, writes["comp_k"])
    comp_v = write_slots(comp_v, writes["comp_v"])

    start_blocks = t0 // c
    out = plan.chunk_prefill_attention(
        q, k, v, comp_k, comp_v, start_blocks,
        block_size=c, block_slots=r, scale=scale_)
    return out, writes


# ---------------------------------------------------------------------------
# Paged, quantized (Linformer-causal) cache
# ---------------------------------------------------------------------------
#
# Same attention math as the compressed cache above, different storage:
#
# * the raw ring buffer is stored quantized (int8, or fp8 where the jnp
#   build has ``float8_e4m3fn``) with one fp32 scale per cached token per
#   KV head (symmetric, amax over Dh);
# * the compressed slot buffer becomes a shared PAGE ARENA: one page holds
#   the r compressed slots of one completed block (page size == the block
#   fold), quantized with one fp32 scale per page per KV head (amax over
#   r·Dh);
# * a per-row page table (B, max_pages) int32 maps a row's block index to a
#   physical arena page; -1 = unallocated. Pages are allocated HOST-side
#   (serving/paged.PageAllocator) between chunks; device code never
#   allocates. A block fold whose table entry is unallocated (or whose
#   block index is out of table range — padded prefill garbage) is
#   redirected to the reserved TRASH page (arena page Np-1), whose contents
#   are never read: slot visibility is bounded by ``glob_ok`` (completed
#   blocks only) and snapshots slice to the row's valid page count.
#
# The page_table leaf carries a leading layer axis like every other leaf
# (broadcast-identical rows) purely so each layer's view has one; no step
# writes it.


def resolve_page_dtype(name: str = "int8"):
    """Map a page-dtype name to (jnp dtype, symmetric qmax).

    ``int8`` is always available; ``fp8`` requires a jnp build with
    ``float8_e4m3fn`` (qmax 448) and raises otherwise so callers can gate.
    """
    if name == "int8":
        return jnp.int8, 127.0
    if name == "fp8":
        fp8 = getattr(jnp, "float8_e4m3fn", None)
        if fp8 is None:
            raise ValueError("fp8 page dtype requires jnp.float8_e4m3fn")
        return fp8, 448.0
    raise ValueError(f"unknown page dtype {name!r} (expected int8|fp8)")


def _qmax_for(dtype) -> float:
    """Symmetric quantization ceiling for a page storage dtype."""
    return 127.0 if dtype == jnp.dtype(jnp.int8) else 448.0


def quantize_blockwise(x: jax.Array, axes, *, dtype=jnp.int8,
                       qmax: float = 127.0) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-block quantization: ``scale = max(amax, eps)/qmax`` over
    the reduced ``axes`` (fp32 math), values rounded+clipped for integer
    dtypes, clipped only for fp8. Returns (q, scale) with the reduced axes
    squeezed out of ``scale``."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = xf / scale
    if jnp.issubdtype(dtype, jnp.integer):
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    else:
        q = jnp.clip(q, -qmax, qmax)
    return q.astype(dtype), jnp.squeeze(scale, axis=axes)


def dequantize_blockwise(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_blockwise` for the cache layouts used here:
    ``scale`` must broadcast against ``q`` once a trailing Dh axis is
    appended (all cache scales reduce exactly the Dh axis plus, for pages,
    the slot axis already repeated back by the gather)."""
    return q.astype(jnp.float32) * scale[..., None]


def paged_cache_spec(
    *, num_layers: int, batch: int, max_seq: int, block_size: int,
    block_slots: int, num_kv_heads: int, head_dim: int,
    arena_pages: Optional[int] = None, page_dtype: str = "int8",
) -> Dict[str, jax.ShapeDtypeStruct]:
    """Spec for the paged, quantized Linformer-causal cache.

    ``arena_pages`` defaults to one full table per row plus the TRASH page
    (capacity-equivalent to the dense pool); serving shrinks it to
    oversubscribe. The last arena page is always reserved as TRASH.
    """
    maxp = max_seq // block_size
    if arena_pages is None:
        arena_pages = batch * maxp + 1
    if arena_pages < 2:
        raise ValueError("arena_pages must be >= 2 (1 usable + TRASH)")
    pdt, _ = resolve_page_dtype(page_dtype)
    L, B, c, r = num_layers, batch, block_size, block_slots
    Hkv, Dh, Np = num_kv_heads, head_dim, arena_pages
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    return {
        "raw_k_q": sd((L, B, c, Hkv, Dh), pdt),
        "raw_v_q": sd((L, B, c, Hkv, Dh), pdt),
        "raw_k_s": sd((L, B, c, Hkv), f32),
        "raw_v_s": sd((L, B, c, Hkv), f32),
        "page_k": sd((L, Np, r, Hkv, Dh), pdt),
        "page_v": sd((L, Np, r, Hkv, Dh), pdt),
        "page_k_s": sd((L, Np, Hkv), f32),
        "page_v_s": sd((L, Np, Hkv), f32),
        "page_table": sd((L, B, maxp), i32),
        "lengths": sd((B,), i32),
    }


def init_paged_cache(**kw) -> Dict[str, jax.Array]:
    """Zero-initialized paged cache; the page table starts all-unallocated
    (-1), NOT zero — page 0 is a real arena page."""
    spec = paged_cache_spec(**kw)
    out = {}
    for k, v in spec.items():
        if k == "page_table":
            out[k] = jnp.full(v.shape, -1, v.dtype)
        else:
            out[k] = jnp.zeros(v.shape, v.dtype)
    return out


def paged_gather(page_q: jax.Array, page_s: jax.Array,
                 page_table: jax.Array, ) -> Tuple[jax.Array, jax.Array]:
    """Gather a row-major dense (B, M, Hkv, Dh) quantized slot view plus
    per-slot scales (B, M, Hkv) from the page arena through the page table.
    Unallocated entries (-1) read page 0's bytes; those slots are never
    visible (``glob_ok`` bounds visibility to allocated, completed blocks)."""
    B, maxp = page_table.shape
    Np, r, Hkv, Dh = page_q.shape
    idx = jnp.clip(page_table, 0, Np - 1)
    gq = page_q[idx].reshape(B, maxp * r, Hkv, Dh)
    gs = jnp.repeat(page_s[idx], r, axis=1)            # (B, maxp·r, Hkv)
    return gq, gs


def _page_writes(dst, k_q, v_q, k_s, v_s) -> Dict[str, SlotWrite]:
    """Whole arena pages: update u writes page dst[u] — payload (r, Hkv,
    Dh) and scales (Hkv,)."""
    zero = jnp.zeros_like(dst)
    return {"page_k": SlotWrite(dst, zero, k_q),
            "page_v": SlotWrite(dst, zero, v_q),
            "page_k_s": SlotWrite(dst, zero, k_s),
            "page_v_s": SlotWrite(dst, zero, v_s)}


def paged_decode_attention(
    q_t: jax.Array,           # (B, 1, H, Dh) — rope already applied at pos t
    k_t: jax.Array,           # (B, 1, Hkv, Dh)
    v_t: jax.Array,
    layer_cache: Dict[str, jax.Array],
    E: jax.Array,             # (c, r) or (Hkv, c, r)
    F: jax.Array,
    t: jax.Array,             # () or (B,) int32 — tokens already cached per row
    *,
    scale: Optional[float] = None,
    plan=None,                # AttentionPlan | backend string | None
) -> Tuple[jax.Array, Dict[str, SlotWrite]]:
    """One decode step over the paged, quantized cache.

    Identical bookkeeping to :func:`compressed_decode_attention` with three
    storage differences: (a) the incoming token is quantized per (row, head)
    into the int8/fp8 ring alongside its scale; (b) attention reads a dense
    gather of the page arena (dequantized INSIDE the kernel on the fused
    path — see ``plan.decode_attention_q``); (c) a completed block's fold is
    re-quantized per (row, head) over (r, Dh) and scattered to the row's
    table page — rows that did not complete a block, or whose block has no
    allocated page, scatter to the TRASH page instead.

    Returns (out, writes): per row the ring token and its scales at t mod c,
    and one arena page (payload and scales) at the row's destination page.
    """
    from repro.parallel.plan import as_plan
    plan = as_plan(plan)
    pk, pv = layer_cache["page_k"], layer_cache["page_v"]
    pk_s, pv_s = layer_cache["page_k_s"], layer_cache["page_v_s"]
    pt = layer_cache["page_table"]
    B, c, Hkv, Dh = layer_cache["raw_k_q"].shape
    Np, r = pk.shape[0], pk.shape[1]
    maxp = pt.shape[1]
    M = maxp * r
    qmax = _qmax_for(pk.dtype)
    trash = Np - 1
    scale_ = scale if scale is not None else Dh ** -0.5

    t = rowwise_t(t, B)
    pos = jnp.mod(t, c)                         # (B,)
    blk = t // c                                # (B,)

    k_q, k_s = quantize_blockwise(k_t, (3,), dtype=pk.dtype, qmax=qmax)
    v_q, v_s = quantize_blockwise(v_t, (3,), dtype=pk.dtype, qmax=qmax)
    writes = {"raw_k_q": row_write(pos, k_q), "raw_v_q": row_write(pos, v_q),
              "raw_k_s": row_write(pos, k_s), "raw_v_s": row_write(pos, v_s)}
    rk_q, rv_q, rk_s, rv_s = (
        write_slots(layer_cache[n], writes[n])
        for n in ("raw_k_q", "raw_v_q", "raw_k_s", "raw_v_s"))

    gk, gk_s = paged_gather(pk, pk_s, pt)
    gv, gv_s = paged_gather(pv, pv_s, pt)
    loc_ok = jnp.arange(c)[None, :] <= pos[:, None]         # (B, c)
    glob_ok = jnp.arange(M)[None, :] < (blk * r)[:, None]   # (B, M)
    out = plan.decode_attention_q(
        q_t, rk_q, rv_q, rk_s, rv_s, gk, gv, gk_s, gv_s,
        loc_ok, glob_ok, scale=scale_)

    # fold a completed block: dequantize the ring, compress, re-quantize per
    # (row, head) over (r, Dh), scatter to the row's table page. Rows not on
    # a fold boundary — or without an allocated page — go to TRASH.
    raw_k_f = dequantize_blockwise(rk_q, rk_s)
    raw_v_f = dequantize_blockwise(rv_q, rv_s)
    Ef, Ff = E.astype(jnp.float32), F.astype(jnp.float32)
    if E.ndim == 2:
        new_ks = jnp.einsum("bchd,cr->brhd", raw_k_f, Ef)
        new_vs = jnp.einsum("bchd,cr->brhd", raw_v_f, Ff)
    else:
        new_ks = jnp.einsum("bchd,hcr->brhd", raw_k_f, Ef)
        new_vs = jnp.einsum("bchd,hcr->brhd", raw_v_f, Ff)
    fk_q, fk_s = quantize_blockwise(new_ks, (1, 3), dtype=pk.dtype, qmax=qmax)
    fv_q, fv_s = quantize_blockwise(new_vs, (1, 3), dtype=pk.dtype, qmax=qmax)

    done = pos == (c - 1)
    pt_blk = jnp.take_along_axis(
        pt, jnp.clip(blk, 0, maxp - 1)[:, None], axis=1)[:, 0]
    commit = done & (pt_blk >= 0) & (blk < maxp)
    dst = jnp.where(commit, pt_blk, trash)                  # (B,)
    writes.update(_page_writes(dst, fk_q, fv_q, fk_s, fv_s))
    return out, writes


def paged_prefill_chunk(
    q: jax.Array,             # (B, P, H, Dh) — one prefill chunk, rope applied
    k: jax.Array,             # (B, P, Hkv, Dh)
    v: jax.Array,
    layer_cache: Dict[str, jax.Array],
    E: jax.Array,             # (c, r) or (Hkv, c, r)
    F: jax.Array,
    t0: jax.Array,            # (B,) int32 — row's current length, multiple of c
    *,
    scale: Optional[float] = None,
    plan=None,                # AttentionPlan | backend string | None
) -> Tuple[jax.Array, Dict[str, SlotWrite]]:
    """One chunked-prefill step over the paged, quantized cache.

    The chunk's P/c block folds are quantized per (row, block, head) and
    scattered to the row's table pages (unallocated or out-of-range blocks —
    padded prefill garbage — go to TRASH). Attention then reads the dense
    gather of the arena taken AFTER the scatter, so a chunk's own earlier
    blocks are visible CACHE-ROUNDED — the same chunked-admission rounding
    contract as the low-precision dense cache (see
    :func:`compressed_prefill_chunk`), one notch coarser. The raw ring is
    untouched, as in the dense path. Returns (out, writes): the chunk's
    pages, payload and scales.
    """
    from repro.parallel.plan import as_plan
    plan = as_plan(plan)
    pdt = layer_cache["page_k"].dtype
    pt = layer_cache["page_table"]
    B, P, Hkv, Dh = k.shape
    c = layer_cache["raw_k_q"].shape[1]
    r = E.shape[-1]
    Np = layer_cache["page_k"].shape[0]
    maxp = pt.shape[1]
    qmax = _qmax_for(pdt)
    trash = Np - 1
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    if P % c != 0:
        raise ValueError(f"prefill chunk P={P} not a multiple of block {c}")
    nb = P // c

    from repro.core.causal import compress_blocks
    kf = k.astype(jnp.float32).reshape(B, nb, c, Hkv, Dh)
    vf = v.astype(jnp.float32).reshape(B, nb, c, Hkv, Dh)
    kbar = compress_blocks(kf, E.astype(jnp.float32))       # (B, nb, r, Hkv, Dh)
    vbar = compress_blocks(vf, F.astype(jnp.float32))
    bk_q, bk_s = quantize_blockwise(kbar, (2, 4), dtype=pdt, qmax=qmax)
    bv_q, bv_s = quantize_blockwise(vbar, (2, 4), dtype=pdt, qmax=qmax)

    t0 = rowwise_t(t0, B)
    blk0 = t0 // c
    abs_blk = blk0[:, None] + jnp.arange(nb)[None, :]       # (B, nb)
    pids = jnp.take_along_axis(pt, jnp.clip(abs_blk, 0, maxp - 1), axis=1)
    dst = jnp.where((pids >= 0) & (abs_blk < maxp), pids, trash).reshape(-1)
    writes = _page_writes(dst, bk_q.reshape(B * nb, r, Hkv, Dh),
                          bv_q.reshape(B * nb, r, Hkv, Dh),
                          bk_s.reshape(B * nb, Hkv), bv_s.reshape(B * nb, Hkv))
    pk, pv, pk_s, pv_s = (write_slots(layer_cache[n], writes[n])
                          for n in ("page_k", "page_v", "page_k_s", "page_v_s"))

    gk, gk_s = paged_gather(pk, pk_s, pt)
    gv, gv_s = paged_gather(pv, pv_s, pt)
    out = plan.chunk_prefill_attention_q(
        q, k, v, gk, gv, gk_s, gv_s, blk0,
        block_size=c, block_slots=r, scale=scale_)
    return out, writes


# ---------------------------------------------------------------------------
# Full KV cache (standard-attention baseline)
# ---------------------------------------------------------------------------


def full_cache_spec(
    *, num_layers: int, batch: int, max_seq: int, num_kv_heads: int,
    head_dim: int, dtype=jnp.bfloat16,
) -> Dict[str, jax.ShapeDtypeStruct]:
    kv = lambda *s: jax.ShapeDtypeStruct(s, dtype)
    return {
        "k": kv(num_layers, batch, max_seq, num_kv_heads, head_dim),
        "v": kv(num_layers, batch, max_seq, num_kv_heads, head_dim),
        "lengths": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def init_full_cache(**kw) -> Dict[str, jax.Array]:
    spec = full_cache_spec(**kw)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in spec.items()}


def full_decode_attention(
    q_t: jax.Array,           # (B, 1, H, Dh)
    k_t: jax.Array,           # (B, 1, Hkv, Dh)
    v_t: jax.Array,
    layer_cache: Dict[str, jax.Array],   # k/v: (B, S, Hkv, Dh)
    t: jax.Array,             # () or (B,) int32 per-row positions
    *,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, Dict[str, SlotWrite]]:
    """One decode step of standard causal attention with a full KV cache.
    Writes and masks are per row; a scalar t broadcasts to all rows.
    Returns (out, writes): each row's token at t[b]."""
    ck, cv = layer_cache["k"], layer_cache["v"]
    B, S, Hkv, Dh = ck.shape
    H = q_t.shape[2]
    G = H // Hkv
    scale_ = scale if scale is not None else Dh ** -0.5
    t = rowwise_t(t, B)
    writes = {"k": row_write(t, k_t), "v": row_write(t, v_t)}
    ck, cv = write_slots(ck, writes["k"]), write_slots(cv, writes["v"])
    qg = q_t.reshape(B, Hkv, G, Dh)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, ck).astype(jnp.float32) * scale_
    ok = jnp.arange(S)[None, :] <= t[:, None]               # (B, S)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q_t.dtype)
    out = jnp.einsum("bhgs,bshd->bhgd", p, cv).reshape(B, 1, H, Dh)
    return out, writes


def full_prefill_chunk(
    q: jax.Array,             # (B, P, H, Dh)
    k: jax.Array,             # (B, P, Hkv, Dh)
    v: jax.Array,
    layer_cache: Dict[str, jax.Array],   # k/v: (B, S, Hkv, Dh)
    t0: jax.Array,            # (B,) int32 — row's current length
    *,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, Dict[str, SlotWrite]]:
    """One chunked-prefill step of standard causal attention with a full KV
    cache: row b's chunk is written at positions [t0[b], t0[b] + P) and each
    query i attends cache positions ≤ t0[b] + i. Padded tail tokens
    (n_valid < P) write garbage the decode path overwrites position-by-
    position before its mask can reach them. Returns (out, writes): each
    row's P tokens at t0[b]."""
    ck, cv = layer_cache["k"], layer_cache["v"]
    B, S, Hkv, Dh = ck.shape
    P = q.shape[1]
    H = q.shape[2]
    G = H // Hkv
    scale_ = scale if scale is not None else Dh ** -0.5
    t0 = rowwise_t(t0, B)
    writes = {"k": row_write(t0, k), "v": row_write(t0, v)}
    ck, cv = write_slots(ck, writes["k"]), write_slots(cv, writes["v"])
    qg = q.reshape(B, P, Hkv, G, Dh)
    s = jnp.einsum("bphgd,bshd->bhgps", qg, ck).astype(jnp.float32) * scale_
    qpos = t0[:, None] + jnp.arange(P)[None, :]              # (B, P)
    ok = jnp.arange(S)[None, None, :] <= qpos[:, :, None]    # (B, P, S)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgps,bshd->bphgd", p, cv).reshape(B, P, H, Dh)
    return out, writes
