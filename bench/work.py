"""Required work of each step and kernel, from shapes alone.

Every function returns the floating-point operations and the bytes of
device memory that the algorithm needs, not what the program happens to
execute: padded rows, padded tokens and recomputation are left out, a cache
is counted by its filled slots and not by what is allocated, and weights
are read once per step. A multiply-add counts two operations. Bytes are
those of the served dtype (`itemsize`).

Shapes are described by a plain dict (`Shapes`), made from a configuration
file under bench/configs by `shapes_from_config`, so nothing here imports
the program or JAX.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Tuple


@dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mlp_mats: int            # 3 for SwiGLU (gate, in, out), 2 otherwise
    block: int = 0           # c, blockwise-causal form
    slots: int = 0           # r, compressed slots per block
    lin_k: int = 0           # k, exact form
    itemsize: int = 2

    @property
    def layer_params(self) -> int:
        D, H, Hkv, Dh = self.d_model, self.heads, self.kv_heads, self.head_dim
        attn = D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D
        return attn + self.mlp_mats * D * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab


def shapes_from_config(cfg: Dict) -> Shapes:
    """A bench/configs file (Hugging Face key names) as `Shapes`."""
    act = cfg.get("hidden_act", "silu")
    return Shapes(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
        head_dim=cfg.get("head_dim",
                         cfg["hidden_size"] // cfg["num_attention_heads"]),
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        mlp_mats=3 if act == "silu" else 2,
        block=cfg.get("linformer_block_size", 0),
        slots=cfg.get("linformer_block_slots", 0),
        lin_k=cfg.get("linformer_k", 0),
        itemsize=2 if cfg.get("dtype", "bfloat16") == "bfloat16" else 4)


# ---------------------------------------------------------------------------
# Blockwise-causal attention (decoder): what position t attends
# ---------------------------------------------------------------------------


def attended(s: Shapes, t: int) -> int:
    """Keys a query at position t attends: its own block up to t, and r
    compressed slots for every earlier block."""
    return t % s.block + 1 + (t // s.block) * s.slots


def attended_sum(s: Shapes, t0: int, n: int) -> int:
    """Σ attended(t) for t in [t0, t0 + n), in closed form per block."""
    c, r = s.block, s.slots
    total, t = 0, t0
    end = t0 + n
    while t < end:
        b, lo = divmod(t, c)
        hi = min(c, lo + end - t)          # positions lo..hi-1 of block b
        total += (hi * (hi + 1) - lo * (lo + 1)) // 2 + (hi - lo) * b * r
        t += hi - lo
    return total


def cache_bytes(s: Shapes, t: int) -> int:
    """Filled cache a decode step at position t reads, all layers: the
    compressed slots of completed blocks and the raw block up to t, keys
    and values."""
    per_slot = 2 * s.kv_heads * s.head_dim * s.itemsize * s.layers
    return per_slot * attended(s, t)


def cache_bytes_allocated(s: Shapes, max_seq: int) -> int:
    """Bytes one pool row holds: M = (max_seq/c)·r compressed slots and a
    c-token raw block, keys and values, all layers."""
    slots = (max_seq // s.block) * s.slots + s.block
    return 2 * s.kv_heads * s.head_dim * s.itemsize * s.layers * slots


def _attn_flops(s: Shapes, n_attended: int) -> int:
    """Scores and weighted values for one query head group over
    n_attended keys, every head, every layer."""
    return 4 * s.heads * s.head_dim * n_attended * s.layers


def _fold_flops(s: Shapes, n_tokens: int) -> int:
    """Compressing keys and values of n_tokens into slots (c·r per block,
    so r per token), every layer."""
    return 4 * s.slots * s.kv_heads * s.head_dim * n_tokens * s.layers


def weight_bytes(s: Shapes) -> int:
    """Layer weights and the LM head, read once per step."""
    return (s.layers * s.layer_params + s.head_params) * s.itemsize


# ---------------------------------------------------------------------------
# Decoder steps
# ---------------------------------------------------------------------------


def decode_step(s: Shapes, positions: Iterable[int]) -> Tuple[int, int]:
    """One decode step of the live rows at `positions` (each row's position
    before the step): (FLOPs, bytes). The LM head runs for every row."""
    positions = list(positions)
    n = len(positions)
    flops = 2 * n * (s.layers * s.layer_params + s.head_params)
    flops += sum(_attn_flops(s, attended(s, t)) for t in positions)
    flops += _fold_flops(s, n)
    act = n * s.d_model * s.itemsize * 2
    byts = weight_bytes(s) + sum(cache_bytes(s, t) for t in positions) + act
    return flops, byts


def prefill(s: Shapes, rows: Iterable[Tuple[int, int]],
            logits_rows: int) -> Tuple[int, int]:
    """Prefill of `rows`, each (offset, n_tokens), in one launch, with the
    LM head for `logits_rows` rows (the rows whose prompt ends here):
    (FLOPs, bytes). Bytes are the weights once, each row's tokens in and
    out of every layer, and the compressed prefix each row reads."""
    rows = list(rows)
    ntok = sum(n for _, n in rows)
    flops = 2 * ntok * s.layers * s.layer_params
    flops += 2 * logits_rows * s.head_params
    flops += sum(_attn_flops(s, attended_sum(s, t0, n)) for t0, n in rows)
    flops += _fold_flops(s, ntok)
    per_slot = 2 * s.kv_heads * s.head_dim * s.itemsize * s.layers
    prefix = sum((t0 // s.block) * s.slots for t0, _ in rows) * per_slot
    act = 2 * ntok * s.d_model * s.itemsize * s.layers
    return flops, weight_bytes(s) + prefix + act


def chunk_prefill_kernel(s: Shapes, rows: Iterable[Tuple[int, int]]
                         ) -> Tuple[int, int]:
    """The chunk-prefill attention kernel over `rows` (offset, n_tokens),
    all layers: (FLOPs, bytes). Bytes: queries, local keys and values,
    the compressed prefix and the chunk's own slots, and the output."""
    flops = byts = 0
    hd = s.head_dim * s.itemsize
    for t0, n in rows:
        flops += _attn_flops(s, attended_sum(s, t0, n))
        slots = ((t0 + n) // s.block) * s.slots
        byts += (2 * n * s.heads * hd + 2 * n * s.kv_heads * hd
                 + 2 * slots * s.kv_heads * hd) * s.layers
    return flops, byts


def decode_kernel(s: Shapes, positions: Iterable[int]) -> Tuple[int, int]:
    """The fused decode attention kernel for live rows at `positions`, all
    layers: (FLOPs, bytes). Bytes are the filled slots, query and output."""
    flops = byts = 0
    for t in positions:
        flops += _attn_flops(s, attended(s, t))
        byts += cache_bytes(s, t) + 2 * s.heads * s.head_dim * s.itemsize \
            * s.layers
    return flops, byts


# ---------------------------------------------------------------------------
# Encoder training (exact Linformer form, MLM)
# ---------------------------------------------------------------------------


def encoder_forward(s: Shapes, batch: int, seq: int,
                    loss_tokens: int) -> int:
    """Forward FLOPs of the exact-form encoder over (batch, seq) with the
    LM head at the `loss_tokens` positions the loss reads: projections and
    MLP per token, the sequence projection of keys and values (E is
    seq × k), scores and values over k slots."""
    ntok = batch * seq
    k = s.lin_k
    flops = 2 * ntok * s.layers * s.layer_params
    flops += 2 * 2 * ntok * k * s.kv_heads * s.head_dim * s.layers
    flops += 4 * ntok * k * s.heads * s.head_dim * s.layers
    flops += 2 * loss_tokens * s.head_params
    return flops


def train_step(s: Shapes, batch: int, seq: int, loss_tokens: int) -> int:
    """Model FLOPs of one training step: forward and backward (twice the
    forward). Recomputation under remat is not counted."""
    return 3 * encoder_forward(s, batch, seq, loss_tokens)


def exact_attention_kernels(s: Shapes, batch: int, seq: int
                            ) -> Tuple[int, int]:
    """The forward Pallas kernels of the exact form in one step, all
    layers: the sequence projection of keys and of values (E shared) and
    the attention over k slots. (FLOPs, bytes)."""
    k, hd = s.lin_k, s.head_dim * s.itemsize
    proj_flops = 2 * 2 * batch * seq * k * s.kv_heads * s.head_dim
    proj_bytes = 2 * (batch * seq * s.kv_heads * hd + seq * k * s.itemsize
                      + batch * k * s.kv_heads * hd)
    attn_flops = 4 * batch * seq * k * s.heads * s.head_dim
    attn_bytes = 2 * batch * seq * s.heads * hd + 2 * batch * k * s.heads * hd
    return ((proj_flops + attn_flops) * s.layers,
            (proj_bytes + attn_bytes) * s.layers)


def roofline_s(flops: float, byts: float, peak: Dict) -> float:
    """Least time the chip could take: the larger of FLOPs at peak FLOP/s
    and bytes at peak bandwidth."""
    return max(flops / peak["flops_per_s"], byts / peak["bytes_per_s"])
