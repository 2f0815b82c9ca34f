"""Mixture-of-Experts feed-forward with capacity-based dispatch.

Expert parallelism: experts are sharded over the "model" mesh axis. Because
activations between blocks are replicated across the model axis (TP layout),
each model-column device routes its local batch against only its *local*
experts and a single psum over "model" combines expert outputs — no explicit
all-to-all is needed; communication is one (tokens × d_model) all-reduce,
identical in shape to a TP FFN reduction.

The same `_moe_local` math runs unsharded (all experts local) for smoke tests
and single-device runs.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MLPConfig, MoEConfig
from repro.models import layers as L
from repro.parallel.sharding import ParallelCtx



def init_moe(rng: jax.Array, d_model: int, cfg: MoEConfig, mlp: MLPConfig,
             dtype) -> Dict:
    ks = jax.random.split(rng, 4)
    E, ff = cfg.num_experts, cfg.expert_d_ff
    p = {
        "router": L.dense_init(ks[0], (d_model, E), jnp.float32),
        "w_in": L.dense_init(ks[1], (E, d_model, ff), dtype),
        "w_out": L.dense_init(ks[2], (E, ff, d_model), dtype),
    }
    if mlp.activation == "swiglu":
        p["w_gate"] = L.dense_init(ks[3], (E, d_model, ff), dtype)
    return p


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    c = int(tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor)
    floor = 1 if cfg.capacity_floor_one else cfg.top_k
    return max(floor, c)


def _expert_ffn(w_in, w_gate, w_out, x, activation: str):
    """x: (E_loc, C, D) -> (E_loc, C, D)."""
    h = jnp.einsum("ecd,edf->ecf", x, w_in)
    if activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w_gate)) * h
    elif activation == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def _moe_local(
    router: jax.Array,       # (D, E_total) fp32
    w_in: jax.Array,         # (E_loc, D, ff)
    w_gate: Optional[jax.Array],
    w_out: jax.Array,        # (E_loc, ff, D)
    x: jax.Array,            # (T, D) local tokens
    *,
    cfg: MoEConfig,
    activation: str,
    e_offset: int,           # global index of first local expert
) -> Tuple[jax.Array, jax.Array]:
    """Route local tokens to local experts. Returns (out (T,D), aux-loss)."""
    T, D = x.shape
    E_total = router.shape[1]
    E_loc = w_in.shape[0]
    C = _capacity(T, cfg)

    logits = (x.astype(jnp.float32) @ router)             # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)        # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e — computed over the
    # full expert set from local tokens; psum-averaging happens via grad sync.
    me = probs.mean(0)                                     # (E,)
    ce = jnp.zeros((E_total,), jnp.float32).at[top_i.reshape(-1)].add(
        jnp.ones((T * cfg.top_k,), jnp.float32)) / (T * cfg.top_k)
    aux = E_total * jnp.sum(me * ce)

    def one_expert(e_local):
        e = e_local + e_offset
        match = (top_i == e)                               # (T, K)
        w_tok = (top_w * match).sum(-1)                    # (T,)
        m_tok = match.any(-1)
        pos = jnp.cumsum(m_tok) - 1                        # position in expert
        keep = m_tok & (pos < C)
        posc = jnp.where(keep, pos, C)                     # C = overflow slot
        buf = jnp.zeros((C + 1, D), x.dtype).at[posc].add(
            jnp.where(keep[:, None], x, 0))
        return buf[:C], (posc, keep, w_tok)

    buf, (posc, keep, w_tok) = jax.vmap(one_expert)(jnp.arange(E_loc))
    y = _expert_ffn(w_in, w_gate, w_out, buf, activation)  # (E_loc, C, D)

    def gather_back(y_e, posc_e, keep_e, w_e):
        y_pad = jnp.concatenate([y_e, jnp.zeros((1, D), y_e.dtype)], 0)
        return y_pad[posc_e] * (w_e * keep_e)[:, None].astype(y_e.dtype)

    out = jax.vmap(gather_back)(y, posc, keep, w_tok).sum(0)  # (T, D)
    return out, aux


def _moe_weight_stationary(
    params: Dict, xt: jax.Array, cfg: MoEConfig, act: str,
    ctx: ParallelCtx,
) -> Tuple[jax.Array, jax.Array]:
    """Decode-time EP where TOKENS move and WEIGHTS stay put.

    Expert weights remain sharded (E over model, D over fsdp axes) — no
    per-step all-gather of the (potentially trillion-param) expert stack.
    Tokens (tiny at decode) are replicated; per-layer collectives are two
    (E_loc, C, ff) psums over the fsdp axes, one (T, D_loc) psum over model
    and a (T, D) token all-gather — bytes independent of parameter count.
    """
    mesh = ctx.mesh
    maxis = ctx.model_axis
    fsdp = ctx.fsdp_axes            # axes the weight D dim is sharded over
    T, D = xt.shape
    E_loc = cfg.num_experts // ctx.model_shards
    C = _capacity(T, cfg)
    w_gate = params.get("w_gate")

    def body(router, w_in, w_gate_, w_out, x_full):
        mi = jax.lax.axis_index(maxis)
        logits = x_full.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, -1)
        top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        D_loc = w_in.shape[1]
        if fsdp:
            di = jax.lax.axis_index(fsdp)
            x_slice = jax.lax.dynamic_slice_in_dim(x_full, di * D_loc, D_loc,
                                                   axis=1)
        else:
            x_slice = x_full

        def one_expert(e_local):
            e = e_local + mi * E_loc
            match = (top_i == e)
            w_tok = (top_w * match).sum(-1)
            m_tok = match.any(-1)
            pos = jnp.cumsum(m_tok) - 1
            keep = m_tok & (pos < C)
            posc = jnp.where(keep, pos, C)
            buf = jnp.zeros((C + 1, D_loc), x_slice.dtype).at[posc].add(
                jnp.where(keep[:, None], x_slice, 0))
            return buf[:C], (posc, keep, w_tok)

        buf, (posc, keep, w_tok) = jax.vmap(one_expert)(jnp.arange(E_loc))
        h = jnp.einsum("ecd,edf->ecf", buf, w_in)        # partial over D_loc
        if act == "swiglu":
            g = jnp.einsum("ecd,edf->ecf", buf, w_gate_)
            if fsdp:
                h = jax.lax.psum(h, fsdp)
                g = jax.lax.psum(g, fsdp)
            h = jax.nn.silu(g) * h
        else:
            if fsdp:
                h = jax.lax.psum(h, fsdp)
            h = jnp.square(jax.nn.relu(h)) if act == "squared_relu" \
                else jax.nn.gelu(h)
        y = jnp.einsum("ecf,efd->ecd", h, w_out)         # (E_loc, C, D_loc)

        def gather_back(y_e, posc_e, keep_e, w_e):
            y_pad = jnp.concatenate([y_e, jnp.zeros((1, D_loc), y_e.dtype)],
                                    0)
            return y_pad[posc_e] * (w_e * keep_e)[:, None].astype(y_e.dtype)

        out = jax.vmap(gather_back)(y, posc, keep, w_tok).sum(0)  # (T, D_loc)
        out = jax.lax.psum(out, maxis)                   # sum expert groups
        if fsdp:
            out = jax.lax.all_gather(out, fsdp, axis=1, tiled=True)
        # aux loss (same formula as _moe_local, computed on full T)
        me = probs.mean(0)
        ce = jnp.zeros((cfg.num_experts,), jnp.float32).at[
            top_i.reshape(-1)].add(1.0) / (T * cfg.top_k)
        aux = cfg.num_experts * jnp.sum(me * ce)
        return out, aux

    fs = fsdp if fsdp else None
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(maxis, fs, None),
                  P(maxis, fs, None) if w_gate is not None else P(),
                  P(maxis, None, fs), P(None, None)),
        out_specs=(P(None, None), P()),
        check_vma=False,
    )(params["router"], params["w_in"],
      w_gate if w_gate is not None else jnp.zeros((), xt.dtype),
      params["w_out"], xt)
    return out, aux


def apply_moe(
    params: Dict,
    x: jax.Array,            # (B, S, D)
    cfg: MoEConfig,
    mlp: MLPConfig,
    ctx: Optional[ParallelCtx] = None,
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN. Returns (out (B,S,D), aux_loss scalar)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    w_gate = params.get("w_gate")
    act = mlp.activation

    if ctx is None or ctx.mesh is None or ctx.model_shards == 1:
        out, aux = _moe_local(params["router"], params["w_in"], w_gate,
                              params["w_out"], xt, cfg=cfg, activation=act,
                              e_offset=0)
        return out.reshape(B, S, D), aux

    if cfg.weight_stationary_decode and S == 1:
        out, aux = _moe_weight_stationary(params, xt, cfg, act, ctx)
        return out.reshape(B, S, D), aux

    mesh = ctx.mesh
    maxis = ctx.model_axis
    daxes = ctx.data_axes
    # decode at tiny batch: tokens can't shard over the data axes — keep them
    # replicated inside the shard_map instead (EP still splits the experts).
    dp_size = 1
    for a in daxes:
        dp_size *= mesh.shape[a]
    if (B * S) % dp_size != 0:
        daxes = ()
    E_loc = cfg.num_experts // ctx.model_shards
    fs = ctx.fsdp_axes or None

    def sharded(router, w_in, w_gate_, w_out, xt_):
        mi = jax.lax.axis_index(maxis)
        out, aux = _moe_local(router, w_in, w_gate_, w_out, xt_, cfg=cfg,
                              activation=act, e_offset=mi * E_loc)
        # combine expert contributions across the EP axis; average the aux
        # loss over every mesh axis so it is truly replicated.
        out = jax.lax.psum(out, maxis)
        aux = jax.lax.pmean(aux, mesh.axis_names)
        return out, aux

    # Expert weights enter replicated along data axes (in_specs trigger the
    # FSDP all-gather here when params are stored fsdp-sharded).
    gate_spec = P(maxis, None, None) if w_gate is not None else P()
    out, aux = jax.shard_map(
        sharded, mesh=mesh,
        in_specs=(P(None, None), P(maxis, None, None), gate_spec,
                  P(maxis, None, None), P(daxes, None)),
        out_specs=(P(daxes, None), P()),
        check_vma=False,
    )(params["router"], params["w_in"],
      w_gate if w_gate is not None else jnp.zeros((), x.dtype),
      params["w_out"], xt)
    return out.reshape(B, S, D), aux
