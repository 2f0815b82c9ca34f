"""Compile every main-path Pallas kernel for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
`v5e:2x2` topology that is described, not attached, at the published widths
of the configs that use it (qwen3-8b for the causal, chunk and decode
forms, linformer-paper for the exact form and the sequence projection) and
at the largest compressed width the wrappers admit, M = MAX_PINNED_SLOTS.
This is what interpret-mode tests cannot check: Mosaic's block-shape rules
and the VMEM budgets. The kernels the benchmark's trace readers find by
name are compiled through their `kernels/ops.py` wrappers too, and each
must come out as an operation of that name.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every pytest worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import blockwise_causal_attn as bca
from repro.kernels import linformer_attn as la
from repro.kernels import ops
from repro.kernels import seq_projection as sp
from repro.kernels.common import MAX_PINNED_SLOTS

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _causal_dims():
    a = get_config("qwen3-8b").attention
    return dict(H=a.num_heads, Hkv=a.num_kv_heads, Dh=a.head_dim,
                c=a.linformer.block_size, r=a.linformer.block_slots)


def _decode(d, B=2, M=MAX_PINNED_SLOTS):
    H, Hkv, Dh, c = d["H"], d["Hkv"], d["Dh"], d["c"]
    G = H // Hkv
    fn = lambda *x: la.decode_attn(*x, scale=Dh ** -0.5)
    return fn, [((B, Hkv, G, Dh), BF16), ((B, Hkv, c, Dh), BF16),
                ((B, Hkv, c, Dh), BF16), ((B, Hkv, M, Dh), BF16),
                ((B, Hkv, M, Dh), BF16), ((B, c), F32), ((B, M), F32)]


def _decode_q(d, B=2, M=MAX_PINNED_SLOTS):
    H, Hkv, Dh, c = d["H"], d["Hkv"], d["Dh"], d["c"]
    G = H // Hkv
    fn = lambda *x: la.decode_attn_q(*x, scale=Dh ** -0.5)
    return fn, [((B, Hkv, G, Dh), BF16), ((B, Hkv, c, Dh), I8),
                ((B, Hkv, c, Dh), I8), ((B, Hkv, M, Dh), I8),
                ((B, Hkv, M, Dh), I8), ((B, Hkv, c), F32),
                ((B, Hkv, c), F32), ((B, Hkv, M), F32), ((B, Hkv, M), F32),
                ((B, c), F32), ((B, M), F32)]


def _prefix(d, residuals=False, B=2, P=512, M=MAX_PINNED_SLOTS):
    H, Hkv, Dh, c, r = (d[k] for k in ("H", "Hkv", "Dh", "c", "r"))
    fn = lambda *x: bca.blockwise_causal_prefix_attn(
        *x, block_size=c, block_slots=r, scale=Dh ** -0.5,
        return_residuals=residuals)
    return fn, [((B, H, P, Dh), BF16), ((B, Hkv, P, Dh), BF16),
                ((B, Hkv, P, Dh), BF16), ((B, Hkv, M, Dh), BF16),
                ((B, Hkv, M, Dh), BF16), ((B,), I32)]


def _prefix_q(d, B=2, P=512, M=MAX_PINNED_SLOTS):
    H, Hkv, Dh, c, r = (d[k] for k in ("H", "Hkv", "Dh", "c", "r"))
    fn = lambda *x: bca.blockwise_causal_prefix_attn_q(
        *x, block_size=c, block_slots=r, scale=Dh ** -0.5)
    return fn, [((B, H, P, Dh), BF16), ((B, Hkv, P, Dh), BF16),
                ((B, Hkv, P, Dh), BF16), ((B, Hkv, M, Dh), I8),
                ((B, Hkv, M, Dh), I8), ((B, Hkv, M), F32),
                ((B, Hkv, M), F32), ((B,), I32)]


def _causal_shapes(d, M=MAX_PINNED_SLOTS):
    H, Hkv, Dh, c, r = (d[k] for k in ("H", "Hkv", "Dh", "c", "r"))
    S = (M // r) * c
    return S, [((1, H, S, Dh), BF16), ((1, Hkv, S, Dh), BF16),
               ((1, Hkv, S, Dh), BF16), ((1, Hkv, M, Dh), BF16),
               ((1, Hkv, M, Dh), BF16)]


def _causal(d, residuals=False):
    _, shapes = _causal_shapes(d)
    fn = lambda *x: bca.blockwise_causal_attn(
        *x, block_size=d["c"], block_slots=d["r"], scale=d["Dh"] ** -0.5,
        return_residuals=residuals)
    return fn, shapes


def _causal_bwd(d):
    S, shapes = _causal_shapes(d)
    fn = lambda *x: bca.blockwise_causal_attn_bwd(
        *x, block_size=d["c"], block_slots=d["r"], scale=d["Dh"] ** -0.5)
    return fn, shapes + [((1, d["H"], S), F32), ((1, d["H"], S), F32),
                         shapes[0]]


def _paper_dims(B=8):
    cfg = get_config("linformer-paper")
    a = cfg.attention
    return B, a.num_heads, cfg.max_seq_len, a.linformer.k, a.head_dim


def _exact(_):
    B, H, n, k, Dh = _paper_dims()
    fn = lambda *x: la.linformer_attn(*x, scale=Dh ** -0.5, block_q=256)
    return fn, [((B, H, n, Dh), BF16), ((B, H, k, Dh), BF16),
                ((B, H, k, Dh), BF16)]


def _seq_projection(_):
    B, H, n, k, Dh = _paper_dims()
    fn = lambda *x: sp.seq_projection(*x, block_s=n)
    return fn, [((B, H, n, Dh), BF16), ((n, k), BF16)]


CASES = {
    "decode_attn": _decode,
    "decode_attn_q": _decode_q,
    "chunk_prefill": _prefix,
    "chunk_prefill_residuals": lambda d: _prefix(d, residuals=True),
    "chunk_prefill_q": _prefix_q,
    "causal_forward": _causal,
    "causal_forward_residuals": lambda d: _causal(d, residuals=True),
    "causal_backward": _causal_bwd,
    "exact_form": _exact,
    "seq_projection": _seq_projection,
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name](_causal_dims())
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _op_decode(d, B=2, M=MAX_PINNED_SLOTS):
    H, Hkv, Dh, c = d["H"], d["Hkv"], d["Dh"], d["c"]
    fn = lambda *x: ops.fused_decode_attention(*x, scale=Dh ** -0.5,
                                               interpret=False)
    return fn, [((B, 1, H, Dh), BF16), ((B, c, Hkv, Dh), BF16),
                ((B, c, Hkv, Dh), BF16), ((B, M, Hkv, Dh), BF16),
                ((B, M, Hkv, Dh), BF16), ((B, c), F32), ((B, M), F32)]


def _op_chunk_prefill(d, B=2, P=512, M=MAX_PINNED_SLOTS):
    H, Hkv, Dh, c, r = (d[k] for k in ("H", "Hkv", "Dh", "c", "r"))
    fn = lambda *x: ops.fused_chunk_prefill_attention(
        *x, block_size=c, block_slots=r, scale=Dh ** -0.5, interpret=False)
    return fn, [((B, P, H, Dh), BF16), ((B, P, Hkv, Dh), BF16),
                ((B, P, Hkv, Dh), BF16), ((B, M, Hkv, Dh), BF16),
                ((B, M, Hkv, Dh), BF16), ((B,), I32)]


def _op_exact(_):
    B, H, n, k, Dh = _paper_dims()
    fn = lambda *x: ops.fused_linformer_attention(*x, scale=Dh ** -0.5,
                                                  interpret=False)
    return fn, [((B, n, H, Dh), BF16), ((B, k, H, Dh), BF16),
                ((B, k, H, Dh), BF16)]


def _op_seq_projection(_):
    B, H, n, k, Dh = _paper_dims()
    fn = lambda *x: ops.fused_seq_projection(*x, interpret=False)
    return fn, [((B, n, H, Dh), BF16), ((n, k), BF16)]


# the operation names bench/metrics/*.py match in a device trace
READER_OPS = {
    "fused_decode_attention": _op_decode,
    "fused_chunk_prefill_attention": _op_chunk_prefill,
    "fused_linformer_attention": _op_exact,
    "fused_seq_projection": _op_seq_projection,
}


@pytest.mark.parametrize("op", list(READER_OPS))
def test_kernel_op_keeps_the_name_readers_match(one_chip, op):
    fn, shapes = READER_OPS[op](_causal_dims())
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = [line.strip().split(" = ", 1)[0] for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all(re.fullmatch(rf"%{op}(\.\d+)?", k)
                           for k in kernels), kernels


def test_decode_chunk_writes_pool_in_place(one_chip, monkeypatch):
    """The serving decode chunk (`model.decode_scan`, fused kernels through
    Mosaic) at qwen3-8b's widths over a 4-row bf16 pool of the serving
    cell's depth (max_seq 32768, M = 2048 slots): inside its loops nothing
    copies, selects over or writes a whole stacked cache leaf or layer slot
    buffer. A copy in the entry computation is a layout change of the
    donated pool, once per call, not per step."""
    import dataclasses

    from repro.models import model as M
    from tests.conftest import hlo_whole_buffer_ops
    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda interpret: bool(interpret))
    a = get_config("qwen3-8b").attention
    cfg = dataclasses.replace(
        get_config("qwen3-8b"), num_layers=2, dtype="bfloat16",
        attention=dataclasses.replace(a, backend="fused"))
    B, max_seq, L = 4, 32768, cfg.num_layers
    c, r = a.linformer.block_size, a.linformer.block_slots
    layer = (B, max_seq // c * r, a.num_kv_heads, a.head_dim)
    whole = {layer, (L,) + layer,
             (L, B, c, a.num_kv_heads, a.head_dim)}
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(spec, jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    cache = jax.tree.map(spec, jax.eval_shape(
        lambda: M.init_cache(cfg, batch=B, max_seq=max_seq, dtype=BF16)))
    rows = lambda dt: jax.ShapeDtypeStruct((B,), dt, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    fn = jax.jit(lambda p, cur, fin, ca, k: M.decode_scan(
        p, cfg, cur, fin, ca, k, n_steps=4, eos_id=0), donate_argnums=(3,))
    hlo = fn.lower(params, rows(I32), rows(jnp.bool_), cache,
                   key).compile().as_text()
    assert "fused_decode_attention" in hlo
    assert [(op, shape) for entry, op, shape in
            hlo_whole_buffer_ops(hlo, whole)
            if not (entry and op == "copy")] == []
