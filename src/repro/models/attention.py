"""Attention block: QKV/output projections + dispatch between the standard
softmax baseline and the paper's Linformer forms.

`init_attention` creates the per-layer parameters (E/F included here when the
sharing mode is per-layer; the layerwise-shared E lives in the model's
"shared" collection and is passed through `shared_lin`).

Compute dispatch: every Linformer form executes through an
:class:`repro.parallel.plan.AttentionPlan` — resolved once per (config,
mesh) and threaded in by the caller (models/transformer.py passes the plan
for its ParallelCtx; a missing plan resolves the config single-device).
The plan owns backend selection (`cfg.backend` "auto" | "reference" |
"fused") AND, under a mesh, the shard_map specs that run the fused Pallas
kernels per shard — this module never branches on backend strings or mesh
presence.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.core import cache as cache_lib
from repro.core import causal as causal_lib
from repro.core import linformer as lin_lib
from repro.models import layers as L
from repro.parallel import plan as plan_lib

NEG_INF = causal_lib.NEG_INF


def init_attention(
    rng: jax.Array, d_model: int, cfg: AttentionConfig, *, max_seq: int,
    dtype,
) -> Dict:
    ks = jax.random.split(rng, 6)
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": L.dense_init(ks[0], (d_model, H * Dh), dtype),
        "wk": L.dense_init(ks[1], (d_model, Hkv * Dh), dtype),
        "wv": L.dense_init(ks[2], (d_model, Hkv * Dh), dtype),
        "wo": L.dense_init(ks[3], (H * Dh, d_model), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * Dh,), dtype)
        p["bk"] = jnp.zeros((Hkv * Dh,), dtype)
        p["bv"] = jnp.zeros((Hkv * Dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(Dh, dtype)
        p["k_norm"] = L.init_rmsnorm(Dh, dtype)
    if cfg.kind in ("linformer", "linformer_causal") \
            and cfg.linformer.sharing != "layerwise":
        # per-layer E/F (num_layers=1: the layer axis is added by the stacker)
        lp = lin_lib.init_linformer_params(ks[4], cfg, num_layers=1,
                                           max_seq=max_seq, dtype=dtype)
        p["lin"] = jax.tree.map(lambda a: a[0], lp["per_layer"])
    return p


def _qkv(params: Dict, x: jax.Array, cfg: AttentionConfig,
         positions: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = L.rms_norm(params["q_norm"], q)
        k = L.rms_norm(params["k_norm"], k)
    if cfg.use_rope:
        pos = positions if positions is not None else jnp.arange(S)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def _resolve_ef(params: Dict, shared_lin: Optional[Dict],
                cfg: AttentionConfig) -> Tuple[jax.Array, jax.Array]:
    if cfg.linformer.sharing == "layerwise":
        assert shared_lin is not None, "layerwise sharing needs shared params"
        E = shared_lin["E"]
        return E, E
    lp = params["lin"]
    return lp["E"], lp.get("F", lp["E"])


def standard_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
    scale: Optional[float] = None,
) -> jax.Array:
    """Full softmax attention (the paper's baseline), GQA-grouped."""
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale_ = scale if scale is not None else Dh ** -0.5
    qg = q.reshape(B, S, Hkv, G, Dh)
    s = jnp.einsum("bshgd,bthd->bhgst", qg, k).astype(jnp.float32) * scale_
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None, None],
                      s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgst,bthd->bshgd", p, v).reshape(B, S, H, Dh)


def apply_attention(
    params: Dict,
    x: jax.Array,
    cfg: AttentionConfig,
    *,
    shared_lin: Optional[Dict] = None,
    positions: Optional[jax.Array] = None,
    chunked: bool = False,
    cache_entry_spec: Optional[Dict] = None,
    plan: Optional[plan_lib.AttentionPlan] = None,
):
    """Full-sequence attention (training / prefill). x: (B, S, D).

    With `cache_entry_spec` = {"max_seq": int, "dtype": ...}, also returns
    this layer's decode-cache entry built from the SAME k/v (single-pass
    prefill — no second forward). `plan` carries the resolved execution
    plan; None resolves the config single-device."""
    B, S, _ = x.shape
    if plan is None:
        plan = plan_lib.resolve_attention_plan(cfg)
    q, k, v = _qkv(params, x, cfg, positions)
    if cfg.kind == "standard":
        out = standard_attention(q, k, v, causal=cfg.causal)
    elif cfg.kind == "linformer":
        E, F = _resolve_ef(params, shared_lin, cfg)
        out = plan.exact_attention(q, k, v, E, F,
                                   projection=cfg.linformer.projection,
                                   scale=cfg.head_dim ** -0.5)
    elif cfg.kind == "linformer_causal":
        E, F = _resolve_ef(params, shared_lin, cfg)
        out = plan.causal_attention(q, k, v, E, F,
                                    block_size=cfg.linformer.block_size,
                                    block_slots=cfg.linformer.block_slots,
                                    scale=cfg.head_dim ** -0.5,
                                    chunked=chunked)
    else:
        raise ValueError(f"unknown attention kind {cfg.kind!r}")
    out = out.reshape(B, S, -1) @ params["wo"]
    if cache_entry_spec is not None:
        entry = _entry_from_kv(k, v, cfg,
                               _resolve_ef(params, shared_lin, cfg)
                               if cfg.kind == "linformer_causal" else None,
                               max_seq=cache_entry_spec["max_seq"],
                               dtype=cache_entry_spec["dtype"])
        return out, entry
    return out


def _entry_from_kv(k, v, cfg: AttentionConfig, ef, *, max_seq, dtype):
    """Decode-cache entry from already-computed k/v (rope applied)."""
    B, S, Hkv, Dh = k.shape
    if cfg.kind == "linformer_causal":
        E, F = ef
        c = cfg.linformer.block_size
        r = cfg.linformer.block_slots
        if S % c != 0:
            raise ValueError(f"prefill length {S} not a multiple of block {c}")
        nb = S // c
        M = (max_seq // c) * r
        comp_k = causal_lib.compress_blocks(
            k.reshape(B, nb, c, Hkv, Dh), E).reshape(B, nb * r, Hkv, Dh)
        comp_v = causal_lib.compress_blocks(
            v.reshape(B, nb, c, Hkv, Dh), F).reshape(B, nb * r, Hkv, Dh)
        pad = ((0, 0), (0, M - nb * r), (0, 0), (0, 0))
        return {
            "raw_k": jnp.zeros((B, c, Hkv, Dh), dtype),
            "raw_v": jnp.zeros((B, c, Hkv, Dh), dtype),
            "comp_k": jnp.pad(comp_k.astype(dtype), pad),
            "comp_v": jnp.pad(comp_v.astype(dtype), pad),
        }
    if cfg.kind == "standard":
        pad = ((0, 0), (0, max_seq - S), (0, 0), (0, 0))
        return {"k": jnp.pad(k.astype(dtype), pad),
                "v": jnp.pad(v.astype(dtype), pad)}
    raise ValueError(f"no decode cache for attention kind {cfg.kind!r}")


def apply_attention_decode(
    params: Dict,
    x_t: jax.Array,                 # (B, 1, D)
    layer_cache: Dict[str, jax.Array],
    t: jax.Array,                   # () or (B,) int32 current position(s)
    cfg: AttentionConfig,
    *,
    shared_lin: Optional[Dict] = None,
    plan: Optional[plan_lib.AttentionPlan] = None,
) -> Tuple[jax.Array, Dict[str, cache_lib.SlotWrite]]:
    """One-token decode step against the layer's cache view. A (B,) t gives
    each row its own position (rope + cache write + mask all per row).
    Returns (out (B, 1, D), writes): the slots this step changes, as
    core/cache.py's :class:`SlotWrite` per leaf."""
    if plan is None:
        plan = plan_lib.resolve_attention_plan(cfg)
    positions = t[None] if t.ndim == 0 else t[:, None]      # (1,) or (B, 1)
    q, k, v = _qkv(params, x_t, cfg, positions=positions)
    if cfg.kind == "linformer_causal":
        E, F = _resolve_ef(params, shared_lin, cfg)
        # paged, quantized cache routes on its page_table leaf — same
        # attention math, different storage (core/cache.py paged family)
        decode_fn = (cache_lib.paged_decode_attention
                     if "page_table" in layer_cache
                     else cache_lib.compressed_decode_attention)
        out, writes = decode_fn(q, k, v, layer_cache, E, F, t, plan=plan)
    elif cfg.kind == "standard":
        out, writes = cache_lib.full_decode_attention(
            q, k, v, layer_cache, t)
    else:
        raise ValueError(
            f"attention kind {cfg.kind!r} has no decode path "
            "(exact linformer is bidirectional/encoder-only)")
    B = x_t.shape[0]
    return out.reshape(B, 1, -1) @ params["wo"], writes


def apply_attention_prefill_chunk(
    params: Dict,
    x: jax.Array,                   # (B, P, D) — one prefill chunk
    layer_cache: Dict[str, jax.Array],
    t0: jax.Array,                  # (B,) int32 — row's committed length
    cfg: AttentionConfig,
    *,
    shared_lin: Optional[Dict] = None,
    positions: Optional[jax.Array] = None,   # (B, P) absolute positions
    plan: Optional[plan_lib.AttentionPlan] = None,
) -> Tuple[jax.Array, Dict[str, cache_lib.SlotWrite]]:
    """Chunked-prefill attention at a per-row offset, against the layer's
    slot-resident cache: row b's chunk covers absolute positions
    [t0[b], t0[b] + P). For linformer_causal t0 and P must be multiples of
    the block size (chunk boundaries are block-fold boundaries); standard
    attention takes any offset. Returns (out (B, P, D'), writes), as
    :func:`apply_attention_decode`."""
    if plan is None:
        plan = plan_lib.resolve_attention_plan(cfg)
    if positions is None:
        positions = t0[:, None] + jnp.arange(x.shape[1])[None, :]
    q, k, v = _qkv(params, x, cfg, positions=positions)
    if cfg.kind == "linformer_causal":
        E, F = _resolve_ef(params, shared_lin, cfg)
        prefill_fn = (cache_lib.paged_prefill_chunk
                      if "page_table" in layer_cache
                      else cache_lib.compressed_prefill_chunk)
        out, writes = prefill_fn(q, k, v, layer_cache, E, F, t0, plan=plan)
    elif cfg.kind == "standard":
        out, writes = cache_lib.full_prefill_chunk(
            q, k, v, layer_cache, t0)
    else:
        raise ValueError(
            f"attention kind {cfg.kind!r} has no chunked-prefill path "
            "(exact linformer is bidirectional/encoder-only)")
    B, P = x.shape[:2]
    return out.reshape(B, P, -1) @ params["wo"], writes


def prefill_cache_entries(
    params: Dict,
    x: jax.Array,                   # (B, S, D) — normed block input
    cfg: AttentionConfig,
    *,
    shared_lin: Optional[Dict],
    max_seq: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jax.Array]:
    """Build this layer's decode-cache entry from a prefilled sequence.

    For the compressed cache, S must be a multiple of block_size (the serving
    engine decodes any remainder tokens individually); the raw ring buffer
    starts empty at t = S.
    """
    q, k, v = _qkv(params, x, cfg, positions=None)
    ef = (_resolve_ef(params, shared_lin, cfg)
          if cfg.kind == "linformer_causal" else None)
    return _entry_from_kv(k, v, cfg, ef, max_seq=max_seq, dtype=dtype)


def decode_cache_spec(cfg: AttentionConfig, *, num_layers: int, batch: int,
                      max_seq: int, dtype=jnp.bfloat16):
    """ShapeDtypeStruct spec of this attention kind's decode cache."""
    if cfg.kind == "linformer_causal":
        return cache_lib.compressed_cache_spec(
            num_layers=num_layers, batch=batch, max_seq=max_seq,
            block_size=cfg.linformer.block_size,
            block_slots=cfg.linformer.block_slots,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim, dtype=dtype)
    return cache_lib.full_cache_spec(
        num_layers=num_layers, batch=batch, max_seq=max_seq,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim, dtype=dtype)


def paged_decode_cache_spec(cfg: AttentionConfig, *, num_layers: int,
                            batch: int, max_seq: int,
                            arena_pages: Optional[int] = None,
                            page_dtype: str = "int8"):
    """ShapeDtypeStruct spec of the paged, quantized decode cache (the
    linformer_causal serving pool in int8/fp8 page storage)."""
    if cfg.kind != "linformer_causal":
        raise ValueError(
            f"paged cache requires kind='linformer_causal', got {cfg.kind!r}")
    return cache_lib.paged_cache_spec(
        num_layers=num_layers, batch=batch, max_seq=max_seq,
        block_size=cfg.linformer.block_size,
        block_slots=cfg.linformer.block_slots,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        arena_pages=arena_pages, page_dtype=page_dtype)
