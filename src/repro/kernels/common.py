"""Shared kernel-wrapper plumbing: layout moves, backend resolution, grid
sizing, the VMEM fail-fast budgets and the per-row operand layout.

One home for the helpers both `kernels/ops.py` (the jit'd shard-local kernel
wrappers) and `parallel/plan.py` (the mesh-aware execution plan) consume —
previously private copies inside ops.py that the plan would have had to
duplicate. Everything here is shape/string logic with no Pallas dependency.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

BACKENDS = ("reference", "fused")
BACKWARD_IMPLS = ("fused", "reference")

# VMEM budgets for operands the kernels pin whole per grid step
# (docs/kernels.md "Known limits"). Exceeding them used to compile anyway and
# blow VMEM (or silently thrash) at runtime — now the wrappers fail fast.
MAX_EXACT_K = 512          # exact form: compressed length of k̄/v̄
MAX_PINNED_SLOTS = 4096    # causal/decode/chunk forms: M = (max_seq/c)·r

# Scoped-VMEM limit of the fused causal backward. It pins (M, Dh) fp32
# accumulators and outputs and holds (c, M) fp32 score-shaped temporaries:
# at M = MAX_PINNED_SLOTS, c = 256, Dh = 128 Mosaic asks for ~22 MiB, above
# the 16 MiB default scoped limit (a v5e core has 128 MiB of VMEM).
BWD_VMEM_LIMIT_BYTES = 32 * 2**20

# Grids tile the sequence into blocks that must divide it evenly; blocks
# below this floor degrade the grid to near-per-row steps (S=509 prime would
# mean a 509-step grid per (batch, head) — pathological in interpret mode and
# a compile-size bomb on TPU), so `divisor_block` refuses them.
MIN_DIVISOR_BLOCK = 8

# Hand-picked perf defaults for the tunable grid knobs — the fallbacks the
# tuning table (repro/tune/table.py, committed TUNING.json) overrides per
# (platform, form, shape bucket). This module is the ONE place these
# literals live (repro-lint RL006): call sites take them from the table
# lookup or leave the kwarg unset.
DEFAULT_BLOCK_Q = 256        # fused_linformer_attention query tile
DEFAULT_BLOCK_S = 512        # fused_seq_projection sequence tile
DEFAULT_Q_CHUNK_BLOCKS = 8   # chunked reference causal form, query blocks


def auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def resolve_backend(backend: str = "auto") -> str:
    """Resolve an `AttentionConfig.backend` knob to a concrete backend.

    "auto" per platform: TPU -> fused (Mosaic-compiled); CPU -> fused in
    interpret mode (how the tests run the kernel logic). Any other platform
    has no Mosaic lowering, so "auto" refuses it rather than quietly
    running something else; pass "reference" to run there on purpose.
    """
    if backend in BACKENDS:
        return backend
    if backend != "auto":
        raise ValueError(
            f"unknown attention backend {backend!r}; "
            f"expected 'auto' or one of {BACKENDS}")
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise ValueError(
            f"attention backend 'auto' serves TPU (Mosaic) and CPU "
            f"(interpret mode), not {platform!r}; set backend='reference' "
            f"to run the pure-jnp path there")
    return "fused"


def resolve_backward_impl(backward_impl: str) -> str:
    if backward_impl not in BACKWARD_IMPLS:
        raise ValueError(
            f"unknown backward_impl {backward_impl!r}; "
            f"expected one of {BACKWARD_IMPLS}")
    return backward_impl


def divisor_block(size: int, preferred: int) -> int:
    """Largest block ≤ preferred that divides `size` (kernels tile evenly).

    Fails fast instead of silently degrading: a sequence length whose largest
    usable divisor is tiny (prime/odd S) would otherwise quietly emit a
    degenerate near-per-row grid. A sub-floor block is only refused when it
    also means a blown-up grid (> MIN_DIVISOR_BLOCK steps) — tiny sequences
    that fit in a handful of blocks are fine."""
    b = max(1, min(preferred, size))
    while size % b:
        b -= 1
    if b < MIN_DIVISOR_BLOCK and size // b > MIN_DIVISOR_BLOCK:
        raise ValueError(
            f"sequence length {size} has no block divisor in "
            f"[{MIN_DIVISOR_BLOCK}, {preferred}] — the kernel grid would "
            f"degrade to {b}-row blocks ({size // b} grid steps per "
            f"(batch, head)). Pad or trim the sequence so it has a divisor "
            f"≥ {MIN_DIVISOR_BLOCK} (any multiple of {MIN_DIVISOR_BLOCK} "
            f"works), or use backend='reference' for this shape.")
    return b


def to_kernel_layout(x):         # (B,S,H,D) -> (B,H,S,D)
    return jnp.moveaxis(x, 2, 1)


def from_kernel_layout(x):
    return jnp.moveaxis(x, 1, 2)


def repeat_kv(x, H):             # (B,Hkv,K,D) -> (B,H,K,D)
    Hkv = x.shape[1]
    if Hkv == H:
        return x
    return jnp.repeat(x, H // Hkv, axis=1)


def rows(x, n: int):
    """Per-row fp32 vectors as (n, 1, L) for a kernel operand: a (1, 1, L)
    block then has its last two dims equal to the array's, which Mosaic
    requires of any L (a (1, L) block of an (n, L) array is refused)."""
    return x.astype(jnp.float32).reshape(n, 1, -1)


def dequant(x, s_row):
    """In-kernel dequantization: (N, Dh) int8/fp8 values times their (1, N)
    fp32 scale row (a `rows` block) → fp32. The reshape turns the lane-dense
    row into a column in VMEM."""
    return x.astype(jnp.float32) * s_row.reshape(-1, 1)
