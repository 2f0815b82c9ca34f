"""Mesh-aware attention execution plan.

ONE place decides how attention executes: which backend (fused Pallas
kernels vs pure-jnp reference), which backward implementation, and — under a
mesh — which mesh axes the fused kernels shard over and with what shard_map
in/out specs. Call sites (models/attention.py, core/cache.py, the trainer
and the serving engine) thread an :class:`AttentionPlan` and never branch on
backend strings or mesh presence themselves; adding a new parallelism
feature means extending the plan, not forking another call site.

Resolution (`resolve_attention_plan`, cached per (config, ctx)):

* backend/backward_impl: the `AttentionConfig` knobs through
  `kernels/common.resolve_backend` (the "auto" platform rule).
* head parallelism (tp): `ctx.model_axis`, when present in the mesh with
  size > 1. The KV-head axis shards — `launch/mesh.validate_attention_mesh`
  fails fast unless tp divides Hkv — and per-head E/F shard with their
  heads; the shared (c, r) / (S, K) projections replicate.
* sequence parallelism (sp): `ctx.seq_axis`, when present with size > 1.
  Each shard keeps its causal blocks RESIDENT and all-gathers only the
  compressed k̄/v̄ prefix ((B, M, D) bytes — the Linformer win;
  core/seq_parallel.py holds the shard-local bodies). The fused backward's
  full-buffer fp32 dk̄/dv̄ accumulators reduce across shards via the
  all-gather transpose (psum-scatter inside the manual region).
* batch: the data-like axes shard the batch dim inside the same manual
  region whenever they divide B (otherwise the batch rides replicated).

Per attention form:

* train fwd/bwd (`causal_attention`, `exact_attention`): tp × sp.
* chunk prefill (`chunk_prefill_attention`): tp; sp additionally shards the
  chunk's query blocks when the chunk length divides (falls back to
  head-parallel-only otherwise — chunks are admission-sized).
* decode (`decode_attention`): tp only — the kernel's two pinned cache
  operands get per-shard slots (Hkv/tp heads); a single query token has no
  sequence to shard, so the sp axis idles at decode (a flash-decode style
  split over the slot axis is a future plan extension, see ROADMAP).

The fused kernels run PER SHARD with purely local shapes — `kernels/ops.py`
wrappers keep their fail-fast shape contracts and never know about meshes.
The manual region is FULL-manual (every mesh axis manual; unused axes ride
replicated), sidestepping the partial-manual + scanned-layers XLA CHECK
documented in train/compressed_dp.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import AttentionConfig
from repro.core import causal as causal_lib
from repro.core import linformer as lin_lib
from repro.core import seq_parallel as sp_lib
from repro.kernels import ops as kernel_ops
from repro.kernels.common import resolve_backend, resolve_backward_impl
from repro.launch.mesh import (axis_size, validate_attention_mesh,
                               validate_seq_shards)
from repro.parallel.sharding import ParallelCtx

# The axis-name registry: every mesh this stack builds (launch/mesh.py) and
# every PartitionSpec it writes draws from these four names. repro-lint's
# RL005 rule (src/repro/analysis/astlint.py, docs/static-analysis.md)
# enforces that no other axis-name literal appears in a spec — add the axis
# HERE first, then use it.
DECLARED_AXES = frozenset({"data", "model", "seq", "pod"})


def _tuned_exact_blocks(q: jax.Array, slots: int) -> Tuple[int, int]:
    """Trace-time tuning-table lookup for the exact form's grid knobs
    (block_q, block_s), keyed on the LOCAL (per-shard) shapes the kernels
    actually launch with. Falls back to kernels/common.py defaults on any
    table miss; shapes are static Python ints so this never traces."""
    from repro.tune import table as tuning
    kw = dict(seq=q.shape[1], slots=slots, heads=q.shape[2],
              dtype=str(q.dtype))
    return tuning.block_q_for(**kw), tuning.block_s_for(**kw)


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    """Resolved execution plan for every attention form of one config on one
    mesh. Frozen + hashable: resolved once per (config, ctx) and threaded
    through trace-time code."""

    backend: str                      # "fused" | "reference" (resolved)
    backward_impl: str = "fused"      # "fused" | "reference"
    mesh: Optional[Mesh] = None
    tp_axis: Optional[str] = None     # mesh axis sharding the (KV-)head dim
    sp_axis: Optional[str] = None     # mesh axis sharding the sequence dim
    data_axes: Tuple[str, ...] = ()   # batch axes inside the manual region

    # -- resolution helpers -------------------------------------------------

    @property
    def fused(self) -> bool:
        return self.backend == "fused"

    @property
    def tp(self) -> int:
        return axis_size(self.mesh, self.tp_axis) if self.tp_axis else 1

    @property
    def sp(self) -> int:
        return axis_size(self.mesh, self.sp_axis) if self.sp_axis else 1

    @property
    def manual(self) -> bool:
        """Whether the fused kernels run per-shard inside shard_map."""
        return self.fused and self.mesh is not None and (
            self.tp > 1 or self.sp > 1)

    def _batch_axes(self, B: int):
        """Data axes shard the batch inside the manual region only when they
        divide it; otherwise the batch rides replicated (correct either way —
        attention is per-row independent)."""
        if not self.data_axes:
            return None
        size = 1
        for a in self.data_axes:
            size *= axis_size(self.mesh, a)
        if size > 1 and B % size == 0:
            return tuple(self.data_axes)
        return None

    def _sp_for(self, S: int, block_size: int, *, required: bool):
        """The sequence axis for an S-token form, or None when sp is off.
        `required=True` (training) fails fast on indivisible shapes;
        `required=False` (chunk prefill) falls back to head-parallel-only."""
        if self.sp <= 1:
            return None
        if S % (self.sp * block_size) != 0:
            if required:
                validate_seq_shards(S, block_size, self.sp, self.sp_axis)
            return None
        return self.sp_axis

    def _ef_spec(self, E: jax.Array) -> P:
        """Per-head E/F (Hkv, c, r) shard with their heads; the shared
        (c, r) projection replicates."""
        if E.ndim == 3:
            return P(self.tp_axis if self.tp > 1 else None, None, None)
        return P(None, None)

    def _head_axis(self):
        return self.tp_axis if self.tp > 1 else None

    def _smap(self, body, in_specs, out_specs):
        return jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # -- train fwd/bwd: blockwise-causal (linformer_causal) -----------------

    def causal_attention(self, q, k, v, E, F, *, block_size: int,
                         block_slots: int, scale: float,
                         chunked: bool = False) -> jax.Array:
        """Full-sequence blockwise-causal attention — the training form,
        differentiable end to end under every sharding the plan resolves.
        q (B, S, H, Dh); k/v (B, S, Hkv, Dh); E/F (c, r) or (Hkv, c, r)."""
        if not self.fused:
            # reference backend: GSPMD partitions the einsums under any mesh
            # (the pre-plan behaviour); `chunked` selects the memory-bounded
            # long-S form exactly as before.
            fn = (causal_lib.blockwise_causal_attention_chunked if chunked
                  else causal_lib.blockwise_causal_attention)
            return fn(q, k, v, E, F, block_size=block_size, scale=scale)
        if not self.manual:
            # the fused kernel streams query blocks itself in BOTH
            # directions (fwd + fused bwd), so `chunked` needs no handling
            # on this path
            return kernel_ops.fused_blockwise_causal_attention(
                q, k, v, E, F, block_size=block_size,
                block_slots=block_slots, scale=scale,
                backward_impl=self.backward_impl)
        B, S, _, _ = q.shape
        sp_axis = self._sp_for(S, block_size, required=True)
        b = self._batch_axes(B)
        tp = self._head_axis()
        qkv_spec = P(b, sp_axis, tp, None)
        espec = self._ef_spec(E)
        bi = self.backward_impl

        def body(q_l, k_l, v_l, E_l, F_l):
            if sp_axis is None:
                return kernel_ops.fused_blockwise_causal_attention(
                    q_l, k_l, v_l, E_l, F_l, block_size=block_size,
                    block_slots=block_slots, scale=scale, backward_impl=bi)
            return sp_lib.sp_blockwise_causal_attention(
                q_l, k_l, v_l, E_l, F_l, seq_axis=sp_axis,
                block_size=block_size, block_slots=block_slots, scale=scale,
                fused=True, backward_impl=bi)

        return self._smap(body, (qkv_spec,) * 3 + (espec, espec),
                          qkv_spec)(q, k, v, E, F)

    # -- train fwd/bwd: exact bidirectional (linformer) ---------------------

    def exact_attention(self, q, k, v, E, F, *, projection: str,
                        scale: float) -> jax.Array:
        """Exact (bidirectional) Linformer attention: sequence projection of
        K/V plus attention over the K compressed slots.

        The manual region covers the paper's default shared linear
        E ∈ R^{S×K} (rows sharded over sp, heads over tp). Per-head / conv /
        pool projections keep the pre-plan behaviour: reference projection +
        fused attention, partitioned by GSPMD."""
        if not self.fused:
            return lin_lib.exact_linformer_attention(q, k, v, E, F,
                                                     kind=projection)
        S = q.shape[1]
        linear_shared = projection == "linear" and E.ndim == 2
        if linear_shared:
            E = E[:S] if E.shape[0] != S else E
            F = F[:S] if F.shape[0] != S else F
        if not self.manual or not linear_shared:
            if linear_shared:
                block_q, block_s = _tuned_exact_blocks(q, E.shape[-1])
                kbar = kernel_ops.fused_seq_projection(k, E, block_s=block_s)
                vbar = kernel_ops.fused_seq_projection(v, F, block_s=block_s)
            else:
                kbar, vbar = lin_lib.project_kv(k, v, E, F, kind=projection)
                block_q, _ = _tuned_exact_blocks(q, kbar.shape[1])
            return kernel_ops.fused_linformer_attention(q, kbar, vbar,
                                                        scale=scale,
                                                        block_q=block_q)
        B = q.shape[0]
        sp_axis = self.sp_axis if (self.sp > 1 and S % self.sp == 0) else None
        b = self._batch_axes(B)
        tp = self._head_axis()
        qkv_spec = P(b, sp_axis, tp, None)
        espec = P(sp_axis, None)

        def body(q_l, k_l, v_l, E_l, F_l):
            if sp_axis is None:
                block_q, block_s = _tuned_exact_blocks(q_l, E_l.shape[-1])
                kbar = kernel_ops.fused_seq_projection(k_l, E_l,
                                                       block_s=block_s)
                vbar = kernel_ops.fused_seq_projection(v_l, F_l,
                                                       block_s=block_s)
                return kernel_ops.fused_linformer_attention(q_l, kbar, vbar,
                                                            scale=scale,
                                                            block_q=block_q)
            return sp_lib.sp_exact_linformer_attention(
                q_l, k_l, v_l, E_l, F_l, seq_axis=sp_axis, scale=scale,
                fused=True)

        return self._smap(body, (qkv_spec,) * 3 + (espec, espec),
                          qkv_spec)(q, k, v, E, F)

    # -- chunk prefill ------------------------------------------------------

    def chunk_prefill_attention(self, q, k, v, comp_k, comp_v, start_blocks,
                                *, block_size: int, block_slots: int,
                                scale: float) -> jax.Array:
        """Prefix-form attention for a prefill chunk at per-row offsets
        against the slot-resident compressed cache. q (B, P, H, Dh); comp_*
        (B, M, Hkv, Dh) full slot buffers; start_blocks (B,) int32."""
        if not self.fused:
            return causal_lib.blockwise_causal_prefix_attention(
                q, k, v, comp_k, comp_v, start_blocks,
                block_size=block_size, block_slots=block_slots, scale=scale)
        if not self.manual:
            return kernel_ops.fused_chunk_prefill_attention(
                q, k, v, comp_k, comp_v, start_blocks,
                block_size=block_size, block_slots=block_slots, scale=scale,
                backward_impl=self.backward_impl)
        B, Pq, _, _ = q.shape
        sp_axis = self._sp_for(Pq, block_size, required=False)
        nb_l = (Pq // self.sp) // block_size if sp_axis else 0
        b = self._batch_axes(B)
        tp = self._head_axis()
        qkv_spec = P(b, sp_axis, tp, None)
        comp_spec = P(b, None, tp, None)    # full pinned buffer per shard

        def body(q_l, k_l, v_l, ck_l, cv_l, sb_l):
            if sp_axis is not None:
                # shard d of the chunk starts nb_l blocks further in
                sb_l = sb_l + jax.lax.axis_index(sp_axis) * nb_l
            return kernel_ops.fused_chunk_prefill_attention(
                q_l, k_l, v_l, ck_l, cv_l, sb_l, block_size=block_size,
                block_slots=block_slots, scale=scale,
                backward_impl=self.backward_impl)

        return self._smap(
            body, (qkv_spec,) * 3 + (comp_spec, comp_spec, P(b)),
            qkv_spec)(q, k, v, comp_k, comp_v, start_blocks)

    # -- decode -------------------------------------------------------------

    def decode_attention(self, q_t, raw_k, raw_v, comp_k, comp_v, loc_ok,
                         glob_ok, *, scale: float) -> jax.Array:
        """Single-token decode attention over [raw ring | compressed slots]
        with per-row validity masks. q_t (B, 1, H, Dh); raw_* (B, c, Hkv,
        Dh); comp_* (B, M, Hkv, Dh); loc_ok (B, c) / glob_ok (B, M) bool."""
        if not self.fused:
            return causal_lib.masked_decode_attention(
                q_t, raw_k, raw_v, comp_k, comp_v, loc_ok, glob_ok,
                scale=scale)
        bias_loc = jnp.where(loc_ok, 0.0,
                             causal_lib.NEG_INF).astype(jnp.float32)
        bias_glob = jnp.where(glob_ok, 0.0,
                              causal_lib.NEG_INF).astype(jnp.float32)
        if not self.manual or self.tp <= 1:
            # decode has no sequence to shard: without tp the sp/data axes
            # ride replicated and the plain per-device call is the plan
            return kernel_ops.fused_decode_attention(
                q_t, raw_k, raw_v, comp_k, comp_v, bias_loc, bias_glob,
                scale=scale)
        B = q_t.shape[0]
        b = self._batch_axes(B)
        tp = self._head_axis()
        kv_spec = P(b, None, tp, None)      # per-shard pinned cache slots

        def body(q_l, rk_l, rv_l, ck_l, cv_l, bl_l, bg_l):
            return kernel_ops.fused_decode_attention(
                q_l, rk_l, rv_l, ck_l, cv_l, bl_l, bg_l, scale=scale)

        return self._smap(
            body,
            (kv_spec, kv_spec, kv_spec, kv_spec, kv_spec,
             P(b, None), P(b, None)),
            kv_spec)(q_t, raw_k, raw_v, comp_k, comp_v, bias_loc, bias_glob)

    # -- decode / chunk prefill, quantized paged cache ----------------------

    def decode_attention_q(self, q_t, raw_k, raw_v, raw_k_s, raw_v_s,
                           comp_k, comp_v, comp_k_s, comp_v_s, loc_ok,
                           glob_ok, *, scale: float) -> jax.Array:
        """Quantized-cache decode: the ring and the page-gathered slots
        arrive in their storage dtype (int8/fp8) with fp32 scales —
        raw_*_s (B, c, Hkv) per token, comp_*_s (B, M, Hkv) per slot. The
        fused path dequantizes INSIDE the kernel; the reference path
        dequantizes in jnp and reuses the dense reference (the parity
        oracle the tolerance bands are measured against). Sharding is the
        dense decode sharding — scales shard with their heads."""
        if not self.fused:
            deq = lambda x, s: x.astype(jnp.float32) * s[..., None]
            return causal_lib.masked_decode_attention(
                q_t, deq(raw_k, raw_k_s), deq(raw_v, raw_v_s),
                deq(comp_k, comp_k_s), deq(comp_v, comp_v_s),
                loc_ok, glob_ok, scale=scale)
        bias_loc = jnp.where(loc_ok, 0.0,
                             causal_lib.NEG_INF).astype(jnp.float32)
        bias_glob = jnp.where(glob_ok, 0.0,
                              causal_lib.NEG_INF).astype(jnp.float32)
        if not self.manual or self.tp <= 1:
            return kernel_ops.fused_decode_attention_q(
                q_t, raw_k, raw_v, raw_k_s, raw_v_s, comp_k, comp_v,
                comp_k_s, comp_v_s, bias_loc, bias_glob, scale=scale)
        B = q_t.shape[0]
        b = self._batch_axes(B)
        tp = self._head_axis()
        kv_spec = P(b, None, tp, None)      # per-shard pinned cache slots
        sc_spec = P(b, None, tp)            # (B, c|M, Hkv) scales

        def body(q_l, rk_l, rv_l, rks_l, rvs_l, ck_l, cv_l, cks_l, cvs_l,
                 bl_l, bg_l):
            return kernel_ops.fused_decode_attention_q(
                q_l, rk_l, rv_l, rks_l, rvs_l, ck_l, cv_l, cks_l, cvs_l,
                bl_l, bg_l, scale=scale)

        return self._smap(
            body,
            (kv_spec, kv_spec, kv_spec, sc_spec, sc_spec, kv_spec, kv_spec,
             sc_spec, sc_spec, P(b, None), P(b, None)),
            kv_spec)(q_t, raw_k, raw_v, raw_k_s, raw_v_s, comp_k, comp_v,
                     comp_k_s, comp_v_s, bias_loc, bias_glob)

    def chunk_prefill_attention_q(self, q, k, v, comp_k, comp_v, comp_k_s,
                                  comp_v_s, start_blocks, *, block_size: int,
                                  block_slots: int, scale: float) -> jax.Array:
        """Quantized-cache chunk prefill: the page-gathered compressed
        buffer stays in its storage dtype with per-slot scales
        (comp_*_s (B, M, Hkv)); the chunk's own K/V are full-precision
        activations. Same sharding shape as the dense chunk prefill."""
        if not self.fused:
            deq = lambda x, s: x.astype(jnp.float32) * s[..., None]
            return causal_lib.blockwise_causal_prefix_attention(
                q, k, v, deq(comp_k, comp_k_s), deq(comp_v, comp_v_s),
                start_blocks, block_size=block_size,
                block_slots=block_slots, scale=scale)
        if not self.manual:
            return kernel_ops.fused_chunk_prefill_attention_q(
                q, k, v, comp_k, comp_v, comp_k_s, comp_v_s, start_blocks,
                block_size=block_size, block_slots=block_slots, scale=scale)
        B, Pq, _, _ = q.shape
        sp_axis = self._sp_for(Pq, block_size, required=False)
        nb_l = (Pq // self.sp) // block_size if sp_axis else 0
        b = self._batch_axes(B)
        tp = self._head_axis()
        qkv_spec = P(b, sp_axis, tp, None)
        comp_spec = P(b, None, tp, None)    # full pinned buffer per shard
        sc_spec = P(b, None, tp)            # (B, M, Hkv) per-slot scales

        def body(q_l, k_l, v_l, ck_l, cv_l, cks_l, cvs_l, sb_l):
            if sp_axis is not None:
                sb_l = sb_l + jax.lax.axis_index(sp_axis) * nb_l
            return kernel_ops.fused_chunk_prefill_attention_q(
                q_l, k_l, v_l, ck_l, cv_l, cks_l, cvs_l, sb_l,
                block_size=block_size, block_slots=block_slots, scale=scale)

        return self._smap(
            body,
            (qkv_spec,) * 3 + (comp_spec, comp_spec, sc_spec, sc_spec, P(b)),
            qkv_spec)(q, k, v, comp_k, comp_v, comp_k_s, comp_v_s,
                      start_blocks)

    # -- cache / batch placement specs --------------------------------------

    def cache_pspecs(self, cache: Dict) -> Dict[str, P]:
        """PartitionSpec per decode-cache leaf: the KV-head axis shards over
        tp — the decode kernel's two pinned operands get PER-SHARD slots —
        everything else (layers, batch rows, slot/ring positions)
        replicated; `lengths` (B,) is host-consulted bookkeeping and stays
        replicated.

        Paged-cache leaves are name-aware: the page table (int32 indices,
        no head axis) replicates; scale leaves (``*_s`` — (..., c|page,
        Hkv), head axis LAST) shard their last axis; quantized payloads
        (ring (L, B, c, Hkv, Dh) and arena (L, Np, r, Hkv, Dh)) follow the
        generic Hkv-at-nd-2 rule."""
        tp = self._head_axis()
        specs = {}
        for name, leaf in cache.items():
            nd = getattr(leaf, "ndim", None) or len(leaf.shape)
            if name == "lengths" or name == "page_table" or nd < 2:
                specs[name] = P(*([None] * nd))
            elif name.endswith("_s"):
                parts = [None] * nd
                parts[nd - 1] = tp          # (..., Hkv) scales
                specs[name] = P(*parts)
            else:
                parts = [None] * nd
                parts[nd - 2] = tp          # (..., Hkv, Dh)
                specs[name] = P(*parts)
        return specs

    def cache_shardings(self, cache: Dict):
        """NamedSharding tree for a pool/decode cache (None without a
        mesh)."""
        if self.mesh is None:
            return None
        return {k: NamedSharding(self.mesh, s)
                for k, s in self.cache_pspecs(cache).items()}

    def place_cache(self, cache: Dict) -> Dict:
        """Lay a freshly initialized cache out per `cache_pspecs` (no-op
        without a mesh) so jit'd consumers inherit the per-shard-slot
        layout instead of re-deciding it per call."""
        sh = self.cache_shardings(cache)
        if sh is None:
            return cache
        return {k: jax.device_put(v, sh[k]) for k, v in cache.items()}


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _resolve_cached(acfg: AttentionConfig,
                    ctx: Optional[ParallelCtx]) -> AttentionPlan:
    backend = resolve_backend(acfg.backend)
    backward_impl = resolve_backward_impl(acfg.backward_impl)
    if ctx is None or ctx.mesh is None:
        return AttentionPlan(backend=backend, backward_impl=backward_impl)
    mesh = ctx.mesh
    tp_axis = (ctx.model_axis
               if axis_size(mesh, ctx.model_axis) > 1 else None)
    sp_axis = (ctx.seq_axis
               if axis_size(mesh, ctx.seq_axis) > 1 else None)
    if backend == "fused" and tp_axis is not None:
        # the model axis is shared (tensor AND expert parallelism): a width
        # that cannot shard Hkv warns and demotes attention to its pre-plan
        # unsharded-fused path instead of sinking the whole model
        if not validate_attention_mesh(
                mesh, num_heads=acfg.num_heads,
                num_kv_heads=acfg.num_kv_heads,
                model_axis=ctx.model_axis):
            tp_axis = None
    return AttentionPlan(backend=backend, backward_impl=backward_impl,
                         mesh=mesh, tp_axis=tp_axis, sp_axis=sp_axis,
                         data_axes=tuple(ctx.data_axes))


def resolve_attention_plan(acfg: AttentionConfig,
                           ctx: Optional[ParallelCtx] = None
                           ) -> AttentionPlan:
    """Resolve the execution plan for one attention config on one parallel
    context — cached, so repeated trace-time resolution is free. Fails fast
    (launch/mesh.py style) when the mesh cannot shard the config."""
    return _resolve_cached(acfg, ctx)


def as_plan(plan: Union["AttentionPlan", str, None]) -> AttentionPlan:
    """Normalize a plan-or-backend-string (the compatibility surface for
    direct kernel-level callers and tests): strings resolve to a
    single-device plan of that backend; None means the reference plan."""
    if isinstance(plan, AttentionPlan):
        return plan
    return AttentionPlan(backend=resolve_backend(plan or "reference"))


# ---------------------------------------------------------------------------
# Batch / pod placement specs (plan-driven spec selection for the trainer
# and the compressed-DP step — previously hand-written at the call sites)
# ---------------------------------------------------------------------------


def data_batch_pspec(ctx: ParallelCtx, ndim: int) -> P:
    """Batch tensors shard their leading dim over the data-like axes."""
    return P(ctx.data_axes if ctx.data_axes else None,
             *([None] * (ndim - 1)))


def pod_stacked_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """A tensor with an explicit leading pod axis (compressed-DP's per-pod
    params/residual stacks): P('pod') on dim 0, replicated elsewhere."""
    return NamedSharding(mesh, P("pod", *([None] * (ndim - 1))))


def pod_batch_sharding(mesh: Mesh, data_axes: Tuple[str, ...],
                       ndim: int) -> NamedSharding:
    """A batch reshaped to (n_pods, per_pod_batch, ...): pod axis leading,
    the per-pod batch over the remaining data axes."""
    return NamedSharding(
        mesh, P("pod", tuple(data_axes) if data_axes else None,
                *([None] * (ndim - 2))))
