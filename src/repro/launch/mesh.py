"""Production mesh definitions.

Defined as FUNCTIONS so importing this module never touches jax device state
(jax locks the device count on first backend initialization — the dry-run
sets XLA_FLAGS before any jax import).

Target: TPU v5e. Single pod = 16×16 = 256 chips, axes ("data", "model").
Multi-pod = 2 pods = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is the outer data-parallel axis whose collectives cross DCN.
"""
from __future__ import annotations

import jax

# v5e hardware constants (roofline §EXPERIMENTS.md)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis `Auto`: GSPMD propagates shardings and
    `with_sharding_constraint` accepts the axes (`jax.make_mesh` defaults
    to `Explicit` axes, which it refuses)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_shards: int = 1, seq_shards: int = 1):
    """Debug mesh over whatever devices exist (tests use 8 host devices).

    `model_shards` is the tensor-parallel ("model") width, `seq_shards` the
    sequence-parallel ("seq") width; the remainder goes to "data". With
    seq_shards == 1 the mesh keeps its historical 2-axis ("data", "model")
    shape, so existing tp-only callers see no change."""
    n = len(jax.devices())
    assert n % (model_shards * seq_shards) == 0, (n, model_shards, seq_shards)
    if seq_shards == 1:
        return make_mesh((n // model_shards, model_shards), ("data", "model"))
    return make_mesh(
        (n // (model_shards * seq_shards), seq_shards, model_shards),
        ("data", "seq", "model"))


def axis_size(mesh, axis: str) -> int:
    """Size of `axis` in `mesh`, 1 if the mesh lacks it (or is None)."""
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


def validate_attention_mesh(mesh, *, num_heads: int, num_kv_heads: int,
                            model_axis: str = "model",
                            strict: bool = False) -> bool:
    """Check whether the mesh can HEAD-SHARD the fused attention kernels,
    with a clear signal when it cannot (mirrors the PR 4 fail-fast wrapper
    style: without this check, an indivisible head count surfaced as a
    shape error deep inside Pallas/shard_map).

    Head parallelism shards the KV-head axis, so the tensor-parallel width
    must divide Hkv (each shard keeps whole GQA groups: H/Hkv is preserved
    per shard automatically once Hkv divides). Returns True when it does.
    When it does not: ``strict=True`` raises; the default warns once and
    returns False — the model axis is SHARED infrastructure (tensor AND
    expert parallelism), so e.g. a 4-wide expert axis over an Hkv=2
    attention must not be fatal: the plan then runs attention on its
    pre-plan unsharded-fused path and only the head sharding is lost."""
    assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
    tp = axis_size(mesh, model_axis)
    if num_kv_heads % tp == 0:
        return True
    msg = (
        f"mesh axis {model_axis!r} has {tp} shards, which does not divide "
        f"num_kv_heads={num_kv_heads}: the fused attention kernels shard "
        f"the KV-head axis, so every shard needs whole KV heads. Use a "
        f"tensor-parallel width that divides {num_kv_heads}, or raise "
        f"num_kv_heads.")
    if strict:
        raise ValueError(msg)
    import warnings
    warnings.warn(msg + " Falling back to unsharded fused attention "
                  "(GSPMD) on this mesh.", stacklevel=2)
    return False


def validate_seq_shards(seq_len: int, block_size: int, sp: int,
                        seq_axis: str = "seq") -> None:
    """Fail fast when a sequence length cannot shard over the sequence axis:
    each shard must hold a whole number of attention blocks."""
    if seq_len % (sp * block_size) != 0:
        raise ValueError(
            f"sequence length {seq_len} cannot shard over mesh axis "
            f"{seq_axis!r} ({sp} shards): each shard must hold a whole "
            f"number of {block_size}-token attention blocks, i.e. S must be "
            f"a multiple of sp·c = {sp * block_size}. Pad the sequence or "
            f"change the mesh.")


# Per-arch FSDP policy: how far parameters/optimizer state are sharded over
# the data-like axes, chosen from per-device memory needs (see DESIGN.md §6).
ARCH_FSDP = {
    "qwen3-8b": "data",
    "qwen3-14b": "data",
    "nemotron-4-15b": "data",
    "qwen1.5-110b": "data",
    "kimi-k2-1t-a32b": "pod_data",
    "qwen3-moe-30b-a3b": "data",
    "internvl2-2b": "none",
    "zamba2-1.2b": "none",
    "musicgen-large": "none",
    "rwkv6-1.6b": "none",
    "linformer-paper": "none",
}


def fsdp_for(arch: str, multi_pod: bool) -> str:
    f = ARCH_FSDP.get(arch, "none")
    if f == "pod_data" and not multi_pod:
        return "data"
    return f
