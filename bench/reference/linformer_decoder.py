"""Plain reference of the blockwise-causal Linformer decoder (qwen3-8b).

Written from the model's published equations in straightforward
jax.numpy, importing nothing of the program: pre-norm RMSNorm blocks; q/k
RMSNorm over the head dimension, then rotary embeddings (half rotation);
grouped-query attention in the blockwise-causal Linformer form: a query at
position t attends its own c-token block causally and r compressed slots
(Eᵀ·keys, Eᵀ·values) of every earlier block, in one softmax; SwiGLU MLP;
final RMSNorm and an untied LM head over the configuration's vocabulary.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import F32, FP32, NEG, Prec, rms, rope


def _blockwise_attention(q, k, v, E, c: int, prec: Prec):
    """q (S, H, Dh), k/v (S, Hkv, Dh), E (c, r); S % c == 0. One query
    block at a time, so memory stays O(c · S/c · r) per block."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    nb, r = S // c, E.shape[1]
    kb = k.reshape(nb, c, Hkv, Dh)
    vb = v.reshape(nb, c, Hkv, Dh)
    kbar = prec.ein("ncgd,cr->nrgd", kb, E).reshape(nb * r, Hkv, Dh)
    vbar = prec.ein("ncgd,cr->nrgd", vb, E).reshape(nb * r, Hkv, Dh)
    qb = q.reshape(nb, c, Hkv, G, Dh)
    scale = Dh ** -0.5
    causal = jnp.tril(jnp.ones((c, c), bool))
    slot_block = jnp.arange(nb * r) // r

    def one(n):
        qn = qb[n]
        s_loc = prec.ein("qhgd,khd->hgqk", qn, kb[n]) * scale
        s_loc = jnp.where(causal, s_loc, NEG)
        s_glob = prec.ein("qhgd,mhd->hgqm", qn, kbar) * scale
        s_glob = jnp.where(slot_block < n, s_glob, NEG)
        p = jax.nn.softmax(jnp.concatenate([s_loc, s_glob], -1), axis=-1)
        out = prec.ein("hgqk,khd->qhgd", p[..., :c], vb[n])
        out = out + prec.ein("hgqm,mhd->qhgd", p[..., c:], vbar)
        return out.reshape(c, H * Dh)

    return jax.lax.map(one, jnp.arange(nb)).reshape(S, H * Dh)


@functools.partial(jax.jit, static_argnames=("shape", "prec"))
def _decoder_layer(lp, E, x, *, shape: Tuple, prec: Prec):
    H, Hkv, Dh, c, theta = shape
    S = x.shape[0]
    a = lp["attn"]
    h = rms(x, lp["ln1"]["scale"])
    q = prec.mm(h, a["wq"]).reshape(S, H, Dh)
    k = prec.mm(h, a["wk"]).reshape(S, Hkv, Dh)
    v = prec.mm(h, a["wv"]).reshape(S, Hkv, Dh)
    q = rms(q, a["q_norm"]["scale"])
    k = rms(k, a["k_norm"]["scale"])
    pos = jnp.arange(S)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    x = x + prec.mm(_blockwise_attention(q, k, v, E, c, prec), a["wo"])
    m = lp["mlp"]
    h = rms(x, lp["ln2"]["scale"])
    g = jax.nn.silu(prec.mm(h, m["w_gate"])) * prec.mm(h, m["w_in"])
    return x + prec.mm(g, m["w_out"])


@functools.partial(jax.jit, static_argnames=("vocab", "prec"))
def _decoder_logits(params, x, idx, *, vocab: int, prec: Prec):
    h = rms(x[idx], params["final_norm"]["scale"])
    return prec.mm(h, params["lm_head"][:, :vocab])


def logits(params, cfg: Dict, tokens: Sequence[int],
           positions: Sequence[int], prec: Prec = FP32) -> np.ndarray:
    """Logits at `positions` of the causal forward over `tokens`, layer by
    layer, for the bench/configs file `cfg`. The sequence is padded at its end (which causality leaves
    without effect on earlier positions) to a power of two up to 4096
    tokens and to a multiple of 4096 above, so few lengths compile.
    Returns (len(positions), vocab)."""
    c = cfg["linformer_block_size"]
    S = max(c, 1 << (len(tokens) - 1).bit_length())
    if S > 4096:
        S = -(-len(tokens) // 4096) * 4096
    toks = np.zeros((S,), np.int32)
    toks[:len(tokens)] = tokens
    key = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
           cfg["head_dim"], c, float(cfg["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][jnp.asarray(toks)].astype(F32)
        E = params["shared"]["lin"]["E"]
        for i in range(cfg["num_hidden_layers"]):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            x = _decoder_layer(lp, E, x, shape=key, prec=prec)
        # a fixed number of positions per call, so one program serves all
        n = len(positions)
        width = 1 << max(0, (n - 1).bit_length())
        idx = np.full((width,), positions[-1], np.int32)
        idx[:n] = positions
        out = _decoder_logits(params, x, jnp.asarray(idx),
                              vocab=cfg["vocab_size"], prec=prec)
    return np.asarray(out[:n])
