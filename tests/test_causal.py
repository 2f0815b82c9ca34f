"""Blockwise-causal Linformer: equivalences + strict causality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (blockwise_causal_attention,
                        blockwise_causal_attention_chunked,
                        compressed_decode_attention, init_compressed_cache)
from tests.conftest import applied_step


def _qkv(B=2, S=32, H=4, Hkv=2, Dh=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, Dh)),
            jax.random.normal(ks[1], (B, S, Hkv, Dh)),
            jax.random.normal(ks[2], (B, S, Hkv, Dh)))


EF = jax.random.normal(jax.random.PRNGKey(42), (8, 4)) * 0.3


class TestParallelForm:
    def test_chunked_equals_unchunked(self):
        q, k, v = _qkv()
        o1 = blockwise_causal_attention(q, k, v, EF, EF, block_size=8)
        o2 = blockwise_causal_attention_chunked(q, k, v, EF, EF, block_size=8,
                                                q_chunk_blocks=2)
        np.testing.assert_allclose(o1, o2, atol=1e-6)

    def test_rejects_non_multiple_length(self):
        q, k, v = _qkv(S=30)
        with pytest.raises(ValueError):
            blockwise_causal_attention(q, k, v, EF, EF, block_size=8)

    def test_strict_causality(self):
        """Perturbing token t must not change outputs at positions < t."""
        q, k, v = _qkv()
        base = blockwise_causal_attention(q, k, v, EF, EF, block_size=8)
        t = 17
        k2 = k.at[:, t:].add(3.0)
        v2 = v.at[:, t:].add(-2.0)
        q2 = q.at[:, t:].add(1.0)
        pert = blockwise_causal_attention(q2, k2, v2, EF, EF, block_size=8)
        np.testing.assert_allclose(base[:, :t], pert[:, :t], atol=1e-6)
        # and the perturbation is visible at position >= t
        assert not np.allclose(base[:, t:], pert[:, t:])

    def test_first_block_is_pure_local(self):
        """Block 0 has no compressed prefix -> exact causal attention."""
        q, k, v = _qkv()
        out = blockwise_causal_attention(q, k, v, EF, EF, block_size=8)
        # reference: standard causal attention on the first 8 positions
        from tests.test_core_linformer import _std_attention
        ref = _std_attention(q[:, :8], k[:, :8], v[:, :8], causal=True)
        np.testing.assert_allclose(out[:, :8], ref, atol=2e-5)

    def test_per_head_projection_shapes(self):
        q, k, v = _qkv()
        Eh = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 4)) * 0.3
        out = blockwise_causal_attention(q, k, v, Eh, Eh, block_size=8)
        assert out.shape == q.shape


class TestDecode:
    def test_stepwise_matches_parallel(self):
        q, k, v = _qkv()
        ref = blockwise_causal_attention(q, k, v, EF, EF, block_size=8)
        cache = init_compressed_cache(
            num_layers=1, batch=2, max_seq=32, block_size=8, block_slots=4,
            num_kv_heads=2, head_dim=8, dtype=jnp.float32)
        lc = {kk: vv[0] for kk, vv in cache.items() if kk != "lengths"}
        outs = []
        for t in range(32):
            o, lc = applied_step(
                compressed_decode_attention,
                q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], lc, EF, EF,
                jnp.int32(t))
            outs.append(o)
        np.testing.assert_allclose(jnp.concatenate(outs, 1), ref, atol=1e-5)

    def test_cache_width_is_compressed(self):
        """The decode cache for n tokens holds c + r*(n/c) slots, not n."""
        S, c, r = 512, 32, 4
        cache = init_compressed_cache(
            num_layers=1, batch=1, max_seq=S, block_size=c, block_slots=r,
            num_kv_heads=2, head_dim=8)
        slots = cache["comp_k"].shape[2] + cache["raw_k"].shape[2]
        assert slots == (S // c) * r + c == 96   # 5.3x smaller than 512

    def test_block_fold_happens_at_boundary(self):
        q, k, v = _qkv(S=16)
        cache = init_compressed_cache(
            num_layers=1, batch=2, max_seq=16, block_size=8, block_slots=4,
            num_kv_heads=2, head_dim=8, dtype=jnp.float32)
        lc = {kk: vv[0] for kk, vv in cache.items() if kk != "lengths"}
        for t in range(7):
            _, lc = applied_step(
                compressed_decode_attention,
                q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], lc, EF, EF,
                jnp.int32(t))
        assert float(jnp.abs(lc["comp_k"]).sum()) == 0.0   # not folded yet
        _, lc = applied_step(
            compressed_decode_attention,
            q[:, 7:8], k[:, 7:8], v[:, 7:8], lc, EF, EF, jnp.int32(7))
        assert float(jnp.abs(lc["comp_k"][:, :4]).sum()) > 0.0  # folded
        assert float(jnp.abs(lc["comp_k"][:, 4:]).sum()) == 0.0
