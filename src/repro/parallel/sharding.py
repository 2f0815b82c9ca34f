"""Sharding rules: map parameter-tree paths and activations to PartitionSpecs.

The mesh has axes ("data", "model") single-pod or ("pod", "data", "model")
multi-pod (launch/mesh.py). Batch always shards over the data-like axes;
parameters shard over "model" (tensor/expert parallel) and optionally over the
data-like axes too (FSDP / ZeRO-3, per-arch `MeshConfig.fsdp`).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Static description of the parallel environment threaded through model
    apply functions. None everywhere = single-device (smoke tests)."""

    mesh: Optional[Mesh] = None
    model_axis: str = "model"
    # mesh axis carrying sequence parallelism for the fused attention plan
    # (parallel/plan.py); absent from the mesh = no sequence sharding
    seq_axis: str = "seq"
    # "none" | "data" | "pod_data" | "experts_data" | "experts_pod_data"
    # ("experts_*": only MoE expert stacks are FSDP-sharded — serving keeps
    #  the small attention/norm weights TP-only so decode never regathers
    #  them; §Perf iteration kimi/decode_32k #3)
    fsdp: str = "none"
    # axes excluded from activation sharding specs (used inside partial-auto
    # shard_map regions where an axis is manual — train/compressed_dp.py)
    exclude_data_axes: Tuple[str, ...] = ()

    @property
    def data_axes(self) -> Tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(a for a in self.mesh.axis_names
                     if a in ("pod", "data")
                     and a not in self.exclude_data_axes)

    @property
    def fsdp_scope(self) -> str:
        return "moe" if self.fsdp.startswith("experts") else "all"

    @property
    def fsdp_axes(self) -> Tuple[str, ...]:
        if self.fsdp in ("data", "experts_data"):
            return ("data",)
        if self.fsdp in ("pod_data", "experts_pod_data"):
            return tuple(a for a in ("pod", "data") if self.mesh is None
                         or a in self.mesh.axis_names)
        return ()

    @property
    def has_pod_axis(self) -> bool:
        """Whether the mesh carries the multi-pod DP axis. Call sites branch
        on THIS (trainer's compressed-DP selection, train/compressed_dp.py's
        precondition) instead of inspecting mesh.axis_names themselves —
        axis introspection stays in the parallel layer (repro-lint RL001)."""
        return self.mesh is not None and "pod" in self.mesh.axis_names

    @property
    def model_shards(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def seq_shards(self) -> int:
        if self.mesh is None or self.seq_axis not in self.mesh.axis_names:
            return 1
        return self.mesh.shape[self.seq_axis]


def shard_activation(x: jax.Array, ctx: Optional[ParallelCtx],
                     spec: Optional[P] = None) -> jax.Array:
    """Constrain an activation's sharding; no-op without a mesh.

    Default spec: batch over the data-like axes, rest replicated.
    """
    if ctx is None or ctx.mesh is None:
        return x
    if spec is None:
        spec = P(ctx.data_axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------

# (regex on '/'.join(path), builder(fsdp_axes) -> PartitionSpec)
# Layer-stacked params carry a leading L axis -> leading None.
# Convention for 2-D matmul weights: contract dim gets FSDP, output dim gets
# TP ("model") for the in-projections; mirrored for out-projections.


def _rules(fsdp):
    F = fsdp if fsdp else None      # tuple of axes or None
    return [
        # embeddings / lm head: vocab over model, d_model over fsdp
        (r"(^|/)embed/tok$", P("model", F)),
        (r"(^|/)embed/pos$", P(None, F)),
        (r"(^|/)lm_head$", P(F, "model")),
        # attention projections (leading L when stacked)
        (r"attn/wq$", P(None, F, "model")),
        (r"attn/wk$", P(None, F, "model")),
        (r"attn/wv$", P(None, F, "model")),
        (r"attn/wo$", P(None, "model", F)),
        (r"attn/b[qkv]$", P(None, "model")),
        # dense MLP
        (r"mlp/w_in$", P(None, F, "model")),
        (r"mlp/w_gate$", P(None, F, "model")),
        (r"mlp/w_out$", P(None, "model", F)),
        # MoE: experts over model (EP), hidden over fsdp
        (r"moe/router$", P(None, F, None)),
        (r"moe/w_in$", P(None, "model", F, None)),
        (r"moe/w_gate$", P(None, "model", F, None)),
        (r"moe/w_out$", P(None, "model", None, F)),
        # mamba2 / rwkv6 big projections
        (r"ssm/w_in$", P(None, F, "model")),
        (r"ssm/w_out$", P(None, "model", F)),
        (r"rwkv/w_(r|k|v|g)$", P(None, F, "model")),
        (r"rwkv/w_o$", P(None, "model", F)),
        (r"rwkv/cm_w_k$", P(None, F, "model")),
        (r"rwkv/cm_w_v$", P(None, "model", F)),
        (r"rwkv/cm_w_r$", P(None, F, "model")),
        # shared (unstacked) attention/mlp block (zamba2): same but no L axis
        (r"shared_block/attn/w[qkv]$", P(F, "model")),
        (r"shared_block/attn/wo$", P("model", F)),
        (r"shared_block/mlp/w_(in|gate)$", P(F, "model")),
        (r"shared_block/mlp/w_out$", P("model", F)),
        # linformer E/F and everything small: replicated
    ]


def spec_for_path(path: str, fsdp_axes: Sequence[str], ndim: int,
                  fsdp_scope: str = "all") -> P:
    fsdp = tuple(fsdp_axes) if fsdp_axes else None
    if fsdp_scope == "moe" and not re.search(r"(^|/)(moe|embed|lm_head)",
                                             path):
        fsdp = None
    for pat, spec in _rules(fsdp):
        if re.search(pat, path):
            # trim/extend to the leaf's rank (shared blocks lack the L axis)
            parts = list(spec)
            if len(parts) > ndim:
                parts = parts[len(parts) - ndim:]
            while len(parts) < ndim:
                parts.append(None)
            # normalize 1-tuples to bare axis names: P(("data",),) and
            # P("data") shard identically but only compare equal once
            # normalized (PartitionSpec equality is structural)
            parts = [p[0] if isinstance(p, tuple) and len(p) == 1 else p
                     for p in parts]
            return P(*parts)
    return P(*([None] * ndim))      # replicate by default


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


def param_shardings(params, ctx: ParallelCtx):
    """PartitionSpec pytree (or NamedSharding pytree if mesh set) matching
    `params` by path rules."""

    def leaf(path, x):
        spec = spec_for_path(_path_str(path), ctx.fsdp_axes, x.ndim,
                             ctx.fsdp_scope)
        if ctx.mesh is None:
            return spec
        return NamedSharding(ctx.mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, params)
