"""Transformer model families: dense decoder LMs (qwen3/nemotron/qwen1.5),
MoE decoders (kimi-k2, qwen3-moe), VLM/audio backbones (internvl2, musicgen)
and the paper's bidirectional encoder (linformer-paper MLM track).

Layers are scanned (stacked params + lax.scan) so HLO size and compile time
are depth-independent; `cfg.scan_layers=False` falls back to an unrolled loop
(needed for non-uniform Linformer k, where per-layer shapes differ).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import cache as cache_lib
from repro.core import linformer as lin_lib
from repro.core.causal import chunked_attention_min_seq
from repro.core.projections import effective_k
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.parallel import plan as plan_lib
from repro.parallel.sharding import ParallelCtx, shard_activation

import dataclasses


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# One transformer block
# ---------------------------------------------------------------------------


def init_block(rng: jax.Array, cfg: ModelConfig, *, lin_k: Optional[int] = None
               ) -> Dict:
    """One decoder/encoder block. `lin_k` overrides the Linformer k (used for
    non-uniform projected dimension in the unrolled encoder)."""
    dt = _dtype(cfg)
    ks = jax.random.split(rng, 3)
    acfg = cfg.attention
    if lin_k is not None:
        acfg = dataclasses.replace(
            acfg, linformer=dataclasses.replace(acfg.linformer, k=lin_k))
    p = {
        "ln1": L.init_rmsnorm(cfg.d_model, dt),
        "ln2": L.init_rmsnorm(cfg.d_model, dt),
        "attn": attn_lib.init_attention(ks[0], cfg.d_model, acfg,
                                        max_seq=cfg.max_seq_len, dtype=dt),
    }
    if cfg.moe.num_experts > 0:
        p["moe"] = moe_lib.init_moe(ks[1], cfg.d_model, cfg.moe, cfg.mlp, dt)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.mlp, dt)
    return p


def _act_spec(ctx: Optional[ParallelCtx], cfg: ModelConfig):
    """Residual-stream sharding between blocks: batch over data axes, and —
    with cfg.seq_shard_activations — the sequence over "model" (sequence
    parallelism for the carry; GSPMD inserts the gather where attention
    needs the full sequence)."""
    if ctx is None or ctx.mesh is None:
        return None
    from jax.sharding import PartitionSpec as P
    if cfg.seq_shard_activations:
        return P(ctx.data_axes, ctx.model_axis, None)
    return P(ctx.data_axes, None, None)


def apply_block(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    shared_lin: Optional[Dict],
    ctx: Optional[ParallelCtx],
    chunked_attn: bool = False,
    cache_entry_spec: Optional[Dict] = None,
):
    """Returns (x, moe_aux_loss[, cache_entry])."""
    spec = _act_spec(ctx, cfg)
    plan = plan_lib.resolve_attention_plan(cfg.attention, ctx)
    res = attn_lib.apply_attention(params["attn"], L.rms_norm(params["ln1"], x),
                                   cfg.attention, shared_lin=shared_lin,
                                   chunked=chunked_attn,
                                   cache_entry_spec=cache_entry_spec,
                                   plan=plan)
    entry = None
    if cache_entry_spec is not None:
        h, entry = res
    else:
        h = res
    x = x + h
    x = shard_activation(x, ctx, spec)
    hin = L.rms_norm(params["ln2"], x)
    if cfg.moe.num_experts > 0:
        h, aux = moe_lib.apply_moe(params["moe"], hin, cfg.moe, cfg.mlp, ctx)
    else:
        h, aux = L.apply_mlp(params["mlp"], hin, cfg.mlp), jnp.zeros((), jnp.float32)
    x = shard_activation(x + h, ctx, spec)
    if cache_entry_spec is not None:
        return x, aux, entry
    return x, aux


def apply_block_decode(
    params: Dict,
    x_t: jax.Array,
    layer_cache: Dict,
    t: jax.Array,
    cfg: ModelConfig,
    *,
    shared_lin: Optional[Dict],
    ctx: Optional[ParallelCtx],
) -> Tuple[jax.Array, Dict, jax.Array]:
    """One block's decode step against the layer's cache view. Returns (x,
    writes, moe_aux): `writes` names the cache slots the step changes."""
    h, writes = attn_lib.apply_attention_decode(
        params["attn"], L.rms_norm(params["ln1"], x_t), layer_cache, t,
        cfg.attention, shared_lin=shared_lin,
        plan=plan_lib.resolve_attention_plan(cfg.attention, ctx))
    x_t = x_t + h
    hin = L.rms_norm(params["ln2"], x_t)
    if cfg.moe.num_experts > 0:
        h, aux = moe_lib.apply_moe(params["moe"], hin, cfg.moe, cfg.mlp, ctx)
    else:
        h, aux = L.apply_mlp(params["mlp"], hin, cfg.mlp), jnp.zeros((), jnp.float32)
    return x_t + h, writes, aux


def apply_block_prefill_chunk(
    params: Dict,
    x: jax.Array,                   # (B, P, D) — one prefill chunk
    layer_cache: Dict,
    t0: jax.Array,                  # (B,) int32 committed per-row lengths
    cfg: ModelConfig,
    *,
    positions: jax.Array,           # (B, P) absolute positions
    shared_lin: Optional[Dict],
    ctx: Optional[ParallelCtx],
) -> Tuple[jax.Array, Dict]:
    """One transformer block over a prefill chunk at a per-row offset
    (decode-path twin of `apply_block`, cache-writing like
    `apply_block_decode` but P tokens at once). Returns (x, writes)."""
    h, writes = attn_lib.apply_attention_prefill_chunk(
        params["attn"], L.rms_norm(params["ln1"], x), layer_cache, t0,
        cfg.attention, shared_lin=shared_lin, positions=positions,
        plan=plan_lib.resolve_attention_plan(cfg.attention, ctx))
    x = x + h
    hin = L.rms_norm(params["ln2"], x)
    if cfg.moe.num_experts > 0:
        h, _ = moe_lib.apply_moe(params["moe"], hin, cfg.moe, cfg.mlp, ctx)
    else:
        h = L.apply_mlp(params["mlp"], hin, cfg.mlp)
    return x + h, writes


def scan_cache_layers(step, x: jax.Array, params: Dict, cfg: ModelConfig,
                      cache: Dict) -> Tuple[jax.Array, Dict]:
    """Run ``step(layer_params, h, layer_view) -> (h, writes)`` over the
    layers, writing each layer's cache in place.

    The stacked cache (every leaf but ``lengths``, layer axis leading) rides
    in the layer scan's carry, not as its xs/ys: layer i reads its view from
    the carry and writes back only the slots ``writes`` names
    (core/cache.py ``write_cache`` at layer i). So a step moves no whole
    layer buffer, and a donated pool is updated in place. Returns (h, the
    stack)."""
    stack = {k: v for k, v in cache.items() if k != "lengths"}

    def body(carry, inp):
        h, st = carry
        lp, i = inp
        view = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
                for k, v in st.items()}
        h, writes = step(lp, h, view)
        return (h, cache_lib.write_cache(st, writes, i)), None

    if cfg.scan_layers:
        (x, stack), _ = jax.lax.scan(
            body, (x, stack),
            (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    else:
        for i, lp in enumerate(params["layers_list"]):
            (x, stack), _ = body((x, stack), (lp, i))
    return x, stack


def prefill_chunk(
    params: Dict,
    cfg: ModelConfig,
    batch_c: Dict,
    cache: Dict,
    n_valid: jax.Array,
    *,
    ctx: Optional[ParallelCtx] = None,
) -> Tuple[jax.Array, Dict]:
    """Prefill-at-offset forward for one fixed-size chunk of every row.

    batch_c: {"tokens": (B, P)} — row b's next prefill chunk, padded at the
    END to the fixed chunk width P; n_valid (B,) int32 counts the real
    tokens (for linformer_causal a multiple of the block size, so padding
    occupies whole blocks and needs no masking — see core/cache.py).

    Row b's chunk starts at its committed length cache["lengths"][b]: rope
    and learned positions are taken at the absolute offsets, the causal
    structure continues from the row's cache (compressed slots / full-cache
    prefix), and each layer's K/V state is written back at the row's offset.
    Returns (last-valid-token logits (B, V), cache advanced by n_valid) —
    the logits row is only meaningful for rows whose prompt ends inside
    this chunk (the serving scheduler samples the first generated token
    from it)."""
    if cfg.embedding_inputs or cfg.frontend_embed_len > 0:
        raise ValueError("chunked prefill supports token inputs only")
    t0 = cache["lengths"]                   # (B,) committed lengths
    tokens = batch_c["tokens"]
    B, P = tokens.shape
    n_valid = jnp.asarray(n_valid, jnp.int32)
    x = L.embed_tokens(params["embed"]["tok"], tokens)
    positions = t0[:, None] + jnp.arange(P)[None, :]         # (B, P)
    if "pos" in params.get("embed", {}):
        tab = params["embed"]["pos"]
        x = x + tab[jnp.clip(positions, 0, tab.shape[0] - 1)]
    x = shard_activation(x, ctx)
    shared_lin = params.get("shared", {}).get("lin")

    x, new_caches = scan_cache_layers(
        lambda lp, h, lc: apply_block_prefill_chunk(
            lp, h, lc, t0, cfg, positions=positions, shared_lin=shared_lin,
            ctx=ctx),
        x, params, cfg, cache)

    # logits only at each row's last REAL token (padded rows' tail is junk)
    h_last = jnp.take_along_axis(
        x, (n_valid - 1)[:, None, None].astype(jnp.int32), axis=1)  # (B,1,D)
    logits = logits_from_hidden(params, cfg, h_last, ctx)
    new_caches["lengths"] = t0 + n_valid
    return logits[:, 0], new_caches


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict:
    dt = _dtype(cfg)
    ks = jax.random.split(rng, 6)
    params: Dict = {"embed": {}}
    if not cfg.embedding_inputs:
        params["embed"]["tok"] = L.init_embedding(ks[0], cfg.padded_vocab_size,
                                                  cfg.d_model, dt)
    if not cfg.attention.use_rope:
        params["embed"]["pos"] = L.init_learned_positions(
            ks[1], cfg.max_seq_len, cfg.d_model, dt)

    lin = cfg.attention.linformer
    uses_linformer = cfg.attention.kind in ("linformer", "linformer_causal")
    if uses_linformer and lin.sharing == "layerwise":
        params["shared"] = {
            "lin": lin_lib.init_linformer_params(
                ks[2], cfg.attention, num_layers=cfg.num_layers,
                max_seq=cfg.max_seq_len, dtype=dt)["shared"]
        }

    if cfg.scan_layers:
        rngs = jax.random.split(ks[3], cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda r: init_block(r, cfg))(rngs)
    else:
        blocks = []
        for i in range(cfg.num_layers):
            k_i = (effective_k(lin.k, lin.k_decay, i, cfg.num_layers)
                   if uses_linformer and cfg.attention.kind == "linformer"
                   else None)
            blocks.append(init_block(jax.random.fold_in(ks[3], i), cfg,
                                     lin_k=k_i))
        params["layers_list"] = blocks

    params["final_norm"] = L.init_rmsnorm(cfg.d_model, dt)
    if cfg.tie_embeddings and not cfg.embedding_inputs:
        pass  # reuse embed.tok
    else:
        params["lm_head"] = L.dense_init(ks[4], (cfg.d_model, cfg.padded_vocab_size),
                                         dt)
    return params


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def embed_inputs(params: Dict, cfg: ModelConfig, batch: Dict,
                 ctx: Optional[ParallelCtx]) -> jax.Array:
    """Assemble the (B, S, D) input stream from tokens and/or stub-frontend
    embeddings (VLM patches prepended; audio frames replace tokens)."""
    if cfg.embedding_inputs:
        x = batch["embeds"].astype(_dtype(cfg))
    else:
        x = L.embed_tokens(params["embed"]["tok"], batch["tokens"])
        if cfg.frontend_embed_len > 0:
            fe = batch["frontend_embeds"].astype(x.dtype)   # (B, P, D)
            x = jnp.concatenate([fe, x], axis=1)
    if "pos" in params.get("embed", {}):
        S = x.shape[1]
        x = x + params["embed"]["pos"][:S][None]
    return shard_activation(x, ctx)


def logits_from_hidden(params: Dict, cfg: ModelConfig, x: jax.Array,
                       ctx: Optional[ParallelCtx]) -> jax.Array:
    x = L.rms_norm(params["final_norm"], x)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"]["tok"].T
    logits = x @ head
    if ctx is not None and ctx.mesh is not None:
        from jax.sharding import PartitionSpec as P
        logits = shard_activation(logits, ctx,
                                  P(ctx.data_axes, None, "model"))
    return logits


def forward(
    params: Dict,
    cfg: ModelConfig,
    batch: Dict,
    *,
    ctx: Optional[ParallelCtx] = None,
    return_cache: bool = False,
    cache_max_seq: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
    return_hidden: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """Full-sequence forward. Returns (logits, moe_aux, cache|None).

    With return_cache=True the sequence length must be a multiple of the
    Linformer block size (standard attention: any length); the returned cache
    is positioned at t = S, ready for decode_step.
    """
    x = embed_inputs(params, cfg, batch, ctx)
    B, S, _ = x.shape
    chunked = S >= chunked_attention_min_seq()
    shared_lin = params.get("shared", {}).get("lin")
    single_pass = return_cache and cfg.single_pass_cache
    entry_spec = ({"max_seq": cache_max_seq or cfg.max_seq_len,
                   "dtype": cache_dtype} if single_pass else None)

    entries = None
    if cfg.scan_layers:
        def body(carry, lp):
            h, aux = carry
            out = apply_block(lp, h, cfg, shared_lin=shared_lin, ctx=ctx,
                              chunked_attn=chunked,
                              cache_entry_spec=entry_spec)
            if single_pass:
                h2, aux2, entry = out
                return (h2, aux + aux2), entry
            h2, aux2 = out
            return (h2, aux + aux2), None

        body = remat_wrap(body, cfg.remat)
        (x, aux), entries = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    else:
        aux = jnp.zeros((), jnp.float32)
        outs = []
        for lp in params["layers_list"]:
            out = apply_block(lp, x, cfg, shared_lin=shared_lin, ctx=ctx,
                              chunked_attn=chunked,
                              cache_entry_spec=entry_spec)
            if single_pass:
                x, a, entry = out
                outs.append(entry)
            else:
                x, a = out
            aux = aux + a
        if single_pass:
            entries = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    logits = x if return_hidden else logits_from_hidden(params, cfg, x, ctx)

    cache = None
    if return_cache:
        if single_pass:
            cache = dict(entries)
            cache["lengths"] = jnp.full((B,), S, jnp.int32)
        else:
            cache = build_cache_from_sequence(
                params, cfg, batch, max_seq=cache_max_seq or cfg.max_seq_len,
                dtype=cache_dtype, ctx=ctx)
    return logits, aux, cache


def build_cache_from_sequence(params, cfg, batch, *, max_seq, dtype, ctx):
    """Recompute per-layer K/V once more to materialize a decode cache after
    prefill (sequence length must be a multiple of the block size for the
    compressed cache). Separate pass keeps the scan body cache-free."""
    x = embed_inputs(params, cfg, batch, ctx)
    B, S, _ = x.shape
    shared_lin = params.get("shared", {}).get("lin")
    acfg = cfg.attention
    chunked = S >= chunked_attention_min_seq()

    def body(carry, lp):
        h, _ = carry
        normed = L.rms_norm(lp["ln1"], h)
        entries = attn_lib.prefill_cache_entries(
            lp["attn"], normed, acfg, shared_lin=shared_lin,
            max_seq=max_seq, dtype=dtype)
        h2, aux2 = apply_block(lp, h, cfg, shared_lin=shared_lin, ctx=ctx,
                               chunked_attn=chunked)
        return (h2, aux2), entries

    body = remat_wrap(body, cfg.remat)
    if cfg.scan_layers:
        _, entries = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                  params["layers"])
    else:
        outs = []
        carry = (x, jnp.zeros((), jnp.float32))
        for lp in params["layers_list"]:
            carry, e = body(carry, lp)
            outs.append(e)
        entries = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    entries["lengths"] = jnp.full((B,), S, jnp.int32)
    return entries


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, *, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Dict:
    spec = attn_lib.decode_cache_spec(cfg.attention, num_layers=cfg.num_layers,
                                      batch=batch, max_seq=max_seq, dtype=dtype)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in spec.items()}


def cache_spec(cfg: ModelConfig, *, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Dict:
    return attn_lib.decode_cache_spec(cfg.attention, num_layers=cfg.num_layers,
                                      batch=batch, max_seq=max_seq, dtype=dtype)


def decode_step(
    params: Dict,
    cfg: ModelConfig,
    batch_t: Dict,
    cache: Dict,
    *,
    ctx: Optional[ParallelCtx] = None,
) -> Tuple[jax.Array, Dict]:
    """One decode step. batch_t: {"tokens": (B,1)} or {"embeds": (B,1,D)}.
    Returns (logits (B,1,V), updated cache). Positions are per row: row b
    decodes at cache["lengths"][b]. The cache is written in place
    (`scan_cache_layers`): per layer, each row's token and, for the
    compressed caches, the r slots of its current block."""
    t = cache["lengths"]                    # (B,) per-row positions
    if cfg.embedding_inputs:
        x = batch_t["embeds"].astype(_dtype(cfg))
    else:
        x = L.embed_tokens(params["embed"]["tok"], batch_t["tokens"])
    if "pos" in params.get("embed", {}):
        x = x + params["embed"]["pos"][t][:, None]      # (B, 1, D)
    x = shard_activation(x, ctx)
    shared_lin = params.get("shared", {}).get("lin")

    x, new_caches = scan_cache_layers(
        lambda lp, h, lc: apply_block_decode(
            lp, h, lc, t, cfg, shared_lin=shared_lin, ctx=ctx)[:2],
        x, params, cfg, cache)

    logits = logits_from_hidden(params, cfg, x, ctx)
    new_caches["lengths"] = t + 1
    return logits, new_caches
