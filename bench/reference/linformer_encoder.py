"""Plain reference of the paper's Linformer MLM encoder (linformer-paper).

Written from the paper's equations in straightforward jax.numpy, importing
nothing of the program: learned positions; pre-norm RMSNorm blocks; the
exact Linformer form softmax(q·(Eᵀk)ᵀ/√d)·(Eᵀv) with one E shared by all
layers and heads; GELU MLP; masked-token cross entropy over the
configuration's vocabulary.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference import F32, FP32, Prec, rms


def _encoder_nll(params, batch, *, shape: Tuple, prec: Prec):
    """Sum of masked-token negative log-likelihoods, and their count."""
    H, Dh, vocab = shape
    toks = batch["tokens"]
    B, S = toks.shape
    x = params["embed"]["tok"][toks].astype(F32)
    x = x + params["embed"]["pos"][:S].astype(F32)[None]
    E = params["shared"]["lin"]["E"][:S].astype(F32)

    def layer(x, lp):
        a = lp["attn"]
        h = rms(x, lp["ln1"]["scale"])
        q = prec.mm(h, a["wq"]).reshape(B, S, H, Dh)
        k = prec.mm(h, a["wk"]).reshape(B, S, H, Dh)
        v = prec.mm(h, a["wv"]).reshape(B, S, H, Dh)
        kbar = prec.ein("bshd,sk->bkhd", k, E)
        vbar = prec.ein("bshd,sk->bkhd", v, E)
        s = prec.ein("bshd,bkhd->bhsk", q, kbar) * Dh ** -0.5
        p = jax.nn.softmax(s, axis=-1)
        o = prec.ein("bhsk,bkhd->bshd", p, vbar).reshape(B, S, H * Dh)
        x = x + prec.mm(o, a["wo"])
        m = lp["mlp"]
        h = rms(x, lp["ln2"]["scale"])
        return x + prec.mm(jax.nn.gelu(prec.mm(h, m["w_in"])), m["w_out"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    h = rms(x, params["final_norm"]["scale"])
    logits = prec.mm(h, params["lm_head"])[..., :vocab]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    w = batch["loss_mask"].astype(F32)
    return jnp.sum((lse - ll) * w), jnp.sum(w)


@functools.partial(jax.jit, static_argnames=("shape", "prec"))
def _nll_and_grad(params, batch, *, shape, prec):
    (nll, cnt), g = jax.value_and_grad(
        lambda p: _encoder_nll(p, batch, shape=shape, prec=prec),
        has_aux=True)(params)
    return nll, cnt, g


def loss_and_grad(params, batch: Dict, cfg: Dict, prec: Prec = FP32,
                  rows: int = 8):
    """Mean masked-token loss over the batch and its float32 gradient, for
    the bench/configs file `cfg`, summed over blocks of `rows` sequences so
    the logits fit."""
    key = (cfg["num_attention_heads"], cfg["head_dim"], cfg["vocab_size"])
    p32 = jax.tree.map(lambda t: t.astype(F32), params)
    B = batch["tokens"].shape[0]
    nll, cnt, grad = 0.0, 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(0, B, rows):
            sub = {k: jnp.asarray(v[i:i + rows]) for k, v in batch.items()}
            n, c, g = _nll_and_grad(p32, sub, shape=key, prec=prec)
            nll, cnt = nll + float(n), cnt + float(c)
            grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    denom = max(cnt, 1.0)
    return nll / denom, jax.tree.map(lambda g: g / denom, grad)
