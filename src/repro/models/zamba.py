"""Zamba2-style hybrid: a trunk of Mamba2 blocks with ONE weight-shared
attention+MLP block invoked every `hybrid_attn_every` trunk layers.

The shared block (where Linformer applies) is stored once in
params["shared_block"]; each invocation keeps its own decode cache.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import cache as cache_lib
from repro.core import linformer as lin_lib
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import mamba2 as m2
from repro.models.transformer import _dtype, remat_wrap
from repro.parallel.sharding import ParallelCtx, shard_activation


def n_attn_invocations(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.hybrid_attn_every


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict:
    dt = _dtype(cfg)
    ks = jax.random.split(rng, 8)
    params: Dict = {
        "embed": {"tok": L.init_embedding(ks[0], cfg.padded_vocab_size, cfg.d_model,
                                          dt)},
    }

    def trunk_layer(r):
        return {"ln": L.init_rmsnorm(cfg.d_model, dt),
                "ssm": m2.init_mamba2(r, cfg.d_model, cfg.ssm, dt)}

    params["trunk"] = jax.vmap(trunk_layer)(
        jax.random.split(ks[1], cfg.num_layers))

    params["shared_block"] = {
        "ln1": L.init_rmsnorm(cfg.d_model, dt),
        "ln2": L.init_rmsnorm(cfg.d_model, dt),
        "attn": attn_lib.init_attention(ks[2], cfg.d_model, cfg.attention,
                                        max_seq=cfg.max_seq_len, dtype=dt),
        "mlp": L.init_mlp(ks[3], cfg.d_model, cfg.mlp, dt),
    }
    if cfg.attention.kind in ("linformer", "linformer_causal") \
            and cfg.attention.linformer.sharing == "layerwise":
        params["shared"] = {"lin": lin_lib.init_linformer_params(
            ks[4], cfg.attention, num_layers=1, max_seq=cfg.max_seq_len,
            dtype=dt)["shared"]}
    params["final_norm"] = L.init_rmsnorm(cfg.d_model, dt)
    params["lm_head"] = L.dense_init(ks[5], (cfg.d_model, cfg.padded_vocab_size), dt)
    return params


def _shared_block(params, cfg, x, *, shared_lin, ctx, chunked):
    sb = params["shared_block"]
    h = attn_lib.apply_attention(sb["attn"], L.rms_norm(sb["ln1"], x),
                                 cfg.attention, shared_lin=shared_lin,
                                 chunked=chunked)
    x = x + h
    x = x + L.apply_mlp(sb["mlp"], L.rms_norm(sb["ln2"], x), cfg.mlp)
    return shard_activation(x, ctx)


def _trunk_slice(params, lo, hi):
    return jax.tree.map(lambda a: a[lo:hi], params["trunk"])


def forward(
    params: Dict, cfg: ModelConfig, batch: Dict, *,
    ctx: Optional[ParallelCtx] = None,
    return_cache: bool = False,
    cache_max_seq: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    x = L.embed_tokens(params["embed"]["tok"], batch["tokens"])
    x = shard_activation(x, ctx)
    B, S, _ = x.shape
    chunked = S >= 8192
    shared_lin = params.get("shared", {}).get("lin")
    every = cfg.hybrid_attn_every
    n_inv = n_attn_invocations(cfg)

    from repro.models.transformer import _act_spec
    spec = _act_spec(ctx, cfg)

    def mamba_body(h, lp):
        y = m2.apply_mamba2(lp["ssm"], L.rms_norm(lp["ln"], h), cfg.ssm,
                            return_state=return_cache)
        if return_cache:
            y, st = y
            h = h + y
            return shard_activation(h, ctx, spec), (
                st["ssm"], st["conv"].astype(cache_dtype))
        return shard_activation(h + y, ctx, spec), None

    mamba_body = remat_wrap(mamba_body, cfg.remat)

    attn_entries = []
    mamba_states = []
    for g in range(n_inv):
        x, st = jax.lax.scan(mamba_body, x,
                             _trunk_slice(params, g * every, (g + 1) * every))
        mamba_states.append(st)
        if return_cache:
            sb = params["shared_block"]
            attn_entries.append(attn_lib.prefill_cache_entries(
                sb["attn"], L.rms_norm(sb["ln1"], x), cfg.attention,
                shared_lin=shared_lin, max_seq=cache_max_seq or cfg.max_seq_len,
                dtype=cache_dtype))
        x = _shared_block(params, cfg, x, shared_lin=shared_lin, ctx=ctx,
                          chunked=chunked)
    if n_inv * every < cfg.num_layers:
        x, st = jax.lax.scan(mamba_body, x,
                             _trunk_slice(params, n_inv * every,
                                          cfg.num_layers))
        mamba_states.append(st)

    from repro.models.transformer import logits_from_hidden
    logits = logits_from_hidden(params, cfg, x, ctx)

    cache = None
    if return_cache:
        # states come stacked per trunk group from the scans — concatenate
        ssm = jnp.concatenate([s[0] for s in mamba_states], axis=0)
        conv = jnp.concatenate([s[1] for s in mamba_states], axis=0)
        cache = {
            "mamba_ssm": ssm,
            "mamba_conv": conv,
            "attn": jax.tree.map(lambda *xs: jnp.stack(xs), *attn_entries),
            "length": jnp.asarray(S, jnp.int32),
        }
    return logits, jnp.zeros((), jnp.float32), cache


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, *, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Dict:
    d_inner, H, P_ = m2.dims(cfg.d_model, cfg.ssm)
    N = cfg.ssm.state_dim
    conv_ch = d_inner + 2 * N
    n_inv = n_attn_invocations(cfg)
    attn_spec = attn_lib.decode_cache_spec(
        cfg.attention, num_layers=n_inv, batch=batch, max_seq=max_seq,
        dtype=dtype)
    return {
        "mamba_ssm": jnp.zeros((cfg.num_layers, batch, H, N, P_), jnp.float32),
        "mamba_conv": jnp.zeros((cfg.num_layers, batch,
                                 cfg.ssm.conv_width - 1, conv_ch), dtype),
        "attn": {k: jnp.zeros(v.shape, v.dtype) for k, v in attn_spec.items()
                 if k != "lengths"},
        "length": jnp.zeros((), jnp.int32),
    }


def decode_step(
    params: Dict, cfg: ModelConfig, batch_t: Dict, cache: Dict, *,
    ctx: Optional[ParallelCtx] = None,
) -> Tuple[jax.Array, Dict]:
    t = cache["length"]
    x = L.embed_tokens(params["embed"]["tok"], batch_t["tokens"])
    shared_lin = params.get("shared", {}).get("lin")
    every = cfg.hybrid_attn_every
    n_inv = n_attn_invocations(cfg)
    new_ssm, new_conv = [], []
    attn = cache["attn"]

    def trunk_step(x, i):
        lp = jax.tree.map(lambda a: a[i], params["trunk"])
        st = {"ssm": cache["mamba_ssm"][i], "conv": cache["mamba_conv"][i]}
        y, st2 = m2.step_mamba2(lp["ssm"], L.rms_norm(lp["ln"], x), st,
                                cfg.ssm)
        new_ssm.append(st2["ssm"])
        new_conv.append(st2["conv"])
        return x + y

    sb = params["shared_block"]
    for g in range(n_inv):
        for i in range(g * every, (g + 1) * every):
            x = trunk_step(x, i)
        lc = jax.tree.map(lambda a: a[g], attn)
        h, writes = attn_lib.apply_attention_decode(
            sb["attn"], L.rms_norm(sb["ln1"], x), lc, t, cfg.attention,
            shared_lin=shared_lin)
        attn = cache_lib.write_cache(attn, writes, g)
        x = x + h
        x = x + L.apply_mlp(sb["mlp"], L.rms_norm(sb["ln2"], x), cfg.mlp)
    for i in range(n_inv * every, cfg.num_layers):
        x = trunk_step(x, i)

    from repro.models.transformer import logits_from_hidden
    logits = logits_from_hidden(params, cfg, x, ctx)
    return logits, {
        "mamba_ssm": jnp.stack(new_ssm),
        "mamba_conv": jnp.stack(new_conv),
        "attn": attn,
        "length": t + 1,
    }
