"""The decode step writes the pool cache in place.

The stacked cache rides in the layer scan's carry, and each layer writes
back only the slots it changes (models/transformer.py `scan_cache_layers`,
core/cache.py `write_cache`). The reference is the whole-buffer update that
this replaced, kept here as plain functions: each layer returns a whole new
layer cache (the block fold committed by a select over the whole slot
buffer), and the layer scan stacks those as its ys.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig, LinformerConfig, ModelConfig
from repro.core import cache as cache_lib
from repro.models import layers as L
from repro.models import model as M
from repro.models import transformer as T
from repro.parallel.plan import as_plan
from tests.conftest import hlo_whole_buffer_ops

B, H, HKV, DH, C, R = 4, 4, 2, 8, 8, 4
MAX_SEQ = 64
EOS = 0


# ---------------------------------------------------------------------------
# The whole-buffer update, as it was
# ---------------------------------------------------------------------------


def _row_update(buf, new, start):
    return jax.vmap(
        lambda b, u, s: jax.lax.dynamic_update_slice_in_dim(b, u, s, axis=0)
    )(buf, new, start)


def _fold(raw, E):
    if E.ndim == 2:
        return jnp.einsum("bchd,cr->brhd", raw, E)
    return jnp.einsum("bchd,hcr->brhd", raw, E)


def old_compressed_decode(q_t, k_t, v_t, lc, E, F, t, *, plan=None):
    plan = as_plan(plan)
    raw_k, raw_v, comp_k, comp_v = (
        lc[n] for n in ("raw_k", "raw_v", "comp_k", "comp_v"))
    b, c, _, dh = raw_k.shape
    m, r = comp_k.shape[1], E.shape[-1]
    t = cache_lib.rowwise_t(t, b)
    pos, blk = jnp.mod(t, c), t // c
    raw_k = _row_update(raw_k, k_t.astype(raw_k.dtype), pos)
    raw_v = _row_update(raw_v, v_t.astype(raw_v.dtype), pos)
    loc_ok = jnp.arange(c)[None, :] <= pos[:, None]
    glob_ok = jnp.arange(m)[None, :] < (blk * r)[:, None]
    out = plan.decode_attention(q_t, raw_k, raw_v, comp_k, comp_v,
                                loc_ok, glob_ok, scale=dh ** -0.5)
    new_ks = _fold(raw_k, E.astype(raw_k.dtype))
    new_vs = _fold(raw_v, F.astype(raw_v.dtype))
    done = (pos == (c - 1))[:, None, None, None]
    comp_k = jnp.where(done, _row_update(comp_k, new_ks, blk * r), comp_k)
    comp_v = jnp.where(done, _row_update(comp_v, new_vs, blk * r), comp_v)
    return out, {"raw_k": raw_k, "raw_v": raw_v,
                 "comp_k": comp_k, "comp_v": comp_v}


def old_paged_decode(q_t, k_t, v_t, lc, E, F, t, *, plan=None):
    plan = as_plan(plan)
    rk_q, rv_q, rk_s, rv_s = (
        lc[n] for n in ("raw_k_q", "raw_v_q", "raw_k_s", "raw_v_s"))
    pk, pv, pk_s, pv_s = (
        lc[n] for n in ("page_k", "page_v", "page_k_s", "page_v_s"))
    pt = lc["page_table"]
    b, c, _, dh = rk_q.shape
    n_pages, r = pk.shape[:2]
    maxp = pt.shape[1]
    qmax = cache_lib._qmax_for(pk.dtype)
    t = cache_lib.rowwise_t(t, b)
    pos, blk = jnp.mod(t, c), t // c
    quant = lambda x, axes: cache_lib.quantize_blockwise(
        x, axes, dtype=pk.dtype, qmax=qmax)
    k_q, k_s = quant(k_t, (3,))
    v_q, v_s = quant(v_t, (3,))
    rk_q, rv_q = _row_update(rk_q, k_q, pos), _row_update(rv_q, v_q, pos)
    rk_s, rv_s = _row_update(rk_s, k_s, pos), _row_update(rv_s, v_s, pos)
    gk, gk_s = cache_lib.paged_gather(pk, pk_s, pt)
    gv, gv_s = cache_lib.paged_gather(pv, pv_s, pt)
    loc_ok = jnp.arange(c)[None, :] <= pos[:, None]
    glob_ok = jnp.arange(maxp * r)[None, :] < (blk * r)[:, None]
    out = plan.decode_attention_q(q_t, rk_q, rv_q, rk_s, rv_s, gk, gv, gk_s,
                                  gv_s, loc_ok, glob_ok, scale=dh ** -0.5)
    deq = cache_lib.dequantize_blockwise
    fk_q, fk_s = quant(_fold(deq(rk_q, rk_s), E.astype(jnp.float32)), (1, 3))
    fv_q, fv_s = quant(_fold(deq(rv_q, rv_s), F.astype(jnp.float32)), (1, 3))
    pt_blk = jnp.take_along_axis(
        pt, jnp.clip(blk, 0, maxp - 1)[:, None], axis=1)[:, 0]
    commit = (pos == (c - 1)) & (pt_blk >= 0) & (blk < maxp)
    dst = jnp.where(commit, pt_blk, n_pages - 1)
    return out, {"raw_k_q": rk_q, "raw_v_q": rv_q,
                 "raw_k_s": rk_s, "raw_v_s": rv_s,
                 "page_k": pk.at[dst].set(fk_q),
                 "page_v": pv.at[dst].set(fv_q),
                 "page_k_s": pk_s.at[dst].set(fk_s),
                 "page_v_s": pv_s.at[dst].set(fv_s),
                 "page_table": pt}


def old_full_decode(q_t, k_t, v_t, lc, t):
    ck, cv = lc["k"], lc["v"]
    b, s, hkv, dh = ck.shape
    h = q_t.shape[2]
    t = cache_lib.rowwise_t(t, b)
    ck = _row_update(ck, k_t.astype(ck.dtype), t)
    cv = _row_update(cv, v_t.astype(cv.dtype), t)
    qg = q_t.reshape(b, hkv, h // hkv, dh)
    sc = jnp.einsum("bhgd,bshd->bhgs", qg, ck).astype(jnp.float32) * dh ** -0.5
    ok = jnp.arange(s)[None, :] <= t[:, None]
    sc = jnp.where(ok[:, None, None, :], sc, cache_lib.NEG_INF)
    p = jax.nn.softmax(sc, axis=-1).astype(q_t.dtype)
    out = jnp.einsum("bhgs,bshd->bhgd", p, cv).reshape(b, 1, h, dh)
    return out, {"k": ck, "v": cv}


def old_decode_step(params, cfg, batch_t, cache, *, ctx=None):
    """The layer scan takes the stacked cache as xs and returns the new one
    as ys (with the old per-layer functions patched in, each layer's
    'writes' is its whole new layer cache)."""
    t = cache["lengths"]
    x = L.embed_tokens(params["embed"]["tok"], batch_t["tokens"])
    shared_lin = params.get("shared", {}).get("lin")
    layer_caches = {k: v for k, v in cache.items() if k != "lengths"}

    def body(h, inp):
        lp, lc = inp
        h2, new_lc, _ = T.apply_block_decode(lp, h, lc, t, cfg,
                                             shared_lin=shared_lin, ctx=ctx)
        return h2, new_lc

    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches))
    logits = T.logits_from_hidden(params, cfg, x, ctx)
    new_caches["lengths"] = t + 1
    return logits, new_caches


# ---------------------------------------------------------------------------
# Pools
# ---------------------------------------------------------------------------


def _cfg(kind, dtype="float32", num_layers=2, max_seq=MAX_SEQ):
    attn = AttentionConfig(kind=kind, backend="reference", num_heads=H,
                           num_kv_heads=HKV, head_dim=DH,
                           linformer=LinformerConfig(block_size=C,
                                                     block_slots=R))
    return ModelConfig(name="inplace-test", num_layers=num_layers, d_model=32,
                       vocab_size=64, max_seq_len=max_seq, attention=attn,
                       dtype=dtype, remat="none")


def _filled(key, spec):
    """Random contents for every cache leaf (the step must carry what it
    does not write bit for bit)."""
    out = {}
    for i, (name, s) in enumerate(sorted(spec.items())):
        k = jax.random.fold_in(key, i)
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[name] = jax.random.randint(k, s.shape, -127, 128, s.dtype)
        elif name.endswith("_s"):
            out[name] = jax.random.uniform(k, s.shape, s.dtype, 0.01, 0.05)
        else:
            out[name] = jax.random.normal(k, s.shape).astype(s.dtype)
    return out


def _pool(kind, cfg, key):
    """A 4-row pool at unequal positions: rows 0 and 2 mid-block, row 1 at a
    block's last token (it folds on its first step), row 3 mid-block two
    blocks in. For the paged pool, row 3 has no page for its current block,
    so its fold goes to the TRASH page."""
    if kind == "paged":
        spec = cache_lib.paged_cache_spec(
            num_layers=cfg.num_layers, batch=B, max_seq=MAX_SEQ,
            block_size=C, block_slots=R, num_kv_heads=HKV, head_dim=DH)
    else:
        spec = T.cache_spec(cfg, batch=B, max_seq=MAX_SEQ,
                            dtype=jnp.bfloat16)
    cache = _filled(key, spec)
    if kind == "paged":
        maxp = MAX_SEQ // C
        perm = jax.random.permutation(jax.random.fold_in(key, 99), B * maxp)
        table = perm.reshape(B, maxp).astype(jnp.int32)
        table = table.at[3, 2:].set(-1)
        cache["page_table"] = jnp.broadcast_to(
            table, (cfg.num_layers, B, maxp))
    cache["lengths"] = jnp.asarray([3, 7, 13, 21], jnp.int32)
    return cache


def _decode(cfg, params, cache, cur, fin, n_steps):
    return M.decode_scan(params, cfg, cur, fin, cache, jax.random.PRNGKey(5),
                         n_steps=n_steps, eos_id=EOS)


# ---------------------------------------------------------------------------
# Tests
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["compressed", "paged", "full"])
def test_decode_matches_whole_buffer_update(kind, monkeypatch):
    """Twelve decode steps over a pool whose rows sit at unequal positions
    (every live row crosses a block boundary; one row is finished, so its
    position stays frozen at a block's last token and it re-folds every
    step): tokens, flags and every cache leaf are bit-identical to the
    whole-buffer update."""
    cfg = _cfg("standard" if kind == "full" else "linformer_causal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    cache = _pool(kind, cfg, jax.random.PRNGKey(1))
    cur = jnp.asarray([5, 9, 17, 33], jnp.int32)
    fin = jnp.asarray([False, True, False, False])

    new = _decode(cfg, params, dict(cache), cur, fin, 12)

    monkeypatch.setattr(cache_lib, "compressed_decode_attention",
                        old_compressed_decode)
    monkeypatch.setattr(cache_lib, "paged_decode_attention", old_paged_decode)
    monkeypatch.setattr(cache_lib, "full_decode_attention", old_full_decode)
    monkeypatch.setattr(T, "decode_step", old_decode_step)
    old = _decode(cfg, params, dict(cache), cur, fin, 12)

    for name, a, b in zip(("tokens", "cur", "finished", "bad"), new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    new_cache, old_cache = new[4], old[4]
    assert set(new_cache) == set(old_cache) == set(cache)
    for name in cache:
        np.testing.assert_array_equal(
            np.asarray(new_cache[name].astype(jnp.float32)),
            np.asarray(old_cache[name].astype(jnp.float32)), name)
    lengths = np.asarray(new_cache["lengths"])
    assert lengths.tolist() == [15, 7, 25, 33]
    assert (lengths[[0, 2, 3]] // C > np.asarray([3, 13, 21]) // C).all()


def test_decode_scan_writes_only_changed_slots():
    """The compiled decode chunk selects over no whole layer slot buffer or
    stacked leaf, and writes none whole: every dynamic-update-slice or
    scatter writes a token or a block's slots. Float32 throughout, so CPU
    float normalisation adds no converts of its own. (Copies of the carried
    stack are checked on the v5e compile, tests/test_tpu_compile.py: the
    CPU backend fuses reads of the old stack past the in-place writes and
    copies the stack to keep it readable, which the TPU compiler does not.)
    """
    n_layers, max_seq = 4, 512
    cfg = _cfg("linformer_causal", num_layers=n_layers, max_seq=max_seq)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    cache = T.init_cache(cfg, batch=B, max_seq=max_seq, dtype=jnp.float32)
    layer = (B, (max_seq // C) * R, HKV, DH)
    whole = {layer, (n_layers,) + layer, (n_layers, B, C, HKV, DH)}
    fn = jax.jit(lambda p, cur, fin, ca, rng: M.decode_scan(
        p, cfg, cur, fin, ca, rng, n_steps=4, eos_id=EOS),
        donate_argnums=(3,))
    hlo = fn.lower(params, jnp.zeros((B,), jnp.int32),
                   jnp.zeros((B,), bool), cache,
                   jax.random.PRNGKey(0)).compile().as_text()
    assert "dynamic-update-slice" in hlo
    assert [(op, shape) for _, op, shape in hlo_whole_buffer_ops(hlo, whole)
            if op != "copy"] == []
