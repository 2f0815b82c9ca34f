"""AttentionPlan: resolution rules, fail-fast mesh validation, and the
multi-device parity suite — fused-under-shard_map == single-device fused ==
reference, for train grads (MHA + GQA), chunk prefill, and decode, on tp,
sp, and tp×sp meshes (subprocesses with 8 forced host devices, like
test_distributed.py).

These are the PR 5 acceptance tests: the fused Pallas kernels run PER SHARD
inside the plan's manual region — head-parallel over the KV-head axis,
sequence-parallel via the all-gathered compressed prefix — and nothing
about the math may change.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESHES = "{'tp2': (2, 1), 'sp2': (1, 2), 'tp2xsp2': (2, 2)}"


def run_py(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# Resolution rules (in-process, single device)
# ---------------------------------------------------------------------------


def test_plan_resolves_single_device():
    from repro.configs.base import AttentionConfig
    from repro.parallel.plan import resolve_attention_plan
    p = resolve_attention_plan(AttentionConfig(backend="auto"))
    assert p.backend == "fused"          # auto -> fused on this container
    assert p.mesh is None and p.tp_axis is None and p.sp_axis is None
    assert not p.manual
    assert p.tp == 1 and p.sp == 1


def test_plan_resolution_is_cached():
    from repro.configs.base import AttentionConfig
    from repro.parallel.plan import resolve_attention_plan
    a = resolve_attention_plan(AttentionConfig())
    b = resolve_attention_plan(AttentionConfig())
    assert a is b


def test_as_plan_normalizes_strings():
    from repro.parallel.plan import AttentionPlan, as_plan
    assert as_plan("fused").backend == "fused"
    assert as_plan("reference").backend == "reference"
    assert as_plan(None).backend == "reference"
    p = as_plan("fused")
    assert as_plan(p) is p
    with pytest.raises(ValueError, match="unknown attention backend"):
        as_plan("mosaic")


def test_validate_seq_shards_fails_fast():
    from repro.launch.mesh import validate_seq_shards
    validate_seq_shards(64, 8, 2)                    # 4 blocks per shard: ok
    with pytest.raises(ValueError, match="whole number of 8-token"):
        validate_seq_shards(24, 8, 2)                # 1.5 blocks per shard


def test_sp_body_rejects_partial_blocks():
    import jax.numpy as jnp
    from repro.core.seq_parallel import sp_blockwise_causal_attention
    x = jnp.zeros((1, 12, 2, 4))
    with pytest.raises(ValueError, match="not a multiple"):
        sp_blockwise_causal_attention(
            x, x, x, jnp.zeros((8, 2)), jnp.zeros((8, 2)), seq_axis="seq",
            block_size=8, block_slots=2, scale=0.5, fused=False)


# ---------------------------------------------------------------------------
# Multi-device parity (subprocess, 8 host devices)
# ---------------------------------------------------------------------------


_COMMON = """
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import (AttentionConfig, LinformerConfig,
                                        ModelConfig)
        from repro.launch.mesh import make_local_mesh
        from repro.models import model as M
        from repro.parallel.plan import resolve_attention_plan
        from repro.parallel.sharding import ParallelCtx, param_shardings

        def cfg_(Hkv, backend="fused"):
            return ModelConfig(
                name="plan-parity", num_layers=2, d_model=32, vocab_size=256,
                max_seq_len=64,
                attention=AttentionConfig(
                    kind="linformer_causal", num_heads=4, num_kv_heads=Hkv,
                    head_dim=8, backend=backend,
                    linformer=LinformerConfig(block_size=8, block_slots=2)),
                dtype="float32", remat="full")

        MESHES = %s
""" % MESHES


@pytest.mark.slow
@pytest.mark.parametrize("hkv", [4, 2])   # MHA, GQA
def test_multi_device_train_grad_parity(hkv):
    """Model-level loss + param grads (incl. E/F through the fused backward)
    under every mesh must match the single-device fused run, which must
    match the reference — the PR 4 parity tolerances."""
    out = run_py(_COMMON + """
        Hkv = %d
        cfg = cfg_(Hkv)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, 256)
        batch = {"tokens": toks, "labels": toks,
                 "loss_mask": jnp.ones((4, 64), jnp.int32)}

        def grads_for(cfg, ctx=None, shardings=None):
            fn = jax.value_and_grad(
                lambda p: M.loss_fn(p, cfg, batch, ctx=ctx)[0])
            fn = jax.jit(fn, in_shardings=(shardings,))
            loss, g = fn(params)
            return float(loss), g

        l_ref, g_ref = grads_for(cfg_(Hkv, backend="reference"))
        l_one, g_one = grads_for(cfg)
        assert abs(l_ref - l_one) < 1e-4, (l_ref, l_one)

        for name, (ms, ss) in MESHES.items():
            mesh = make_local_mesh(model_shards=ms, seq_shards=ss)
            ctx = ParallelCtx(mesh=mesh, fsdp="data")
            plan = resolve_attention_plan(cfg.attention, ctx)
            assert plan.manual, name
            with mesh:
                l_m, g_m = grads_for(cfg, ctx=ctx,
                                     shardings=param_shardings(params, ctx))
            assert abs(l_m - l_one) < 1e-5, (name, l_m, l_one)
            for (pa, a), (pb, b) in zip(
                    jax.tree_util.tree_leaves_with_path(g_m),
                    jax.tree_util.tree_leaves_with_path(g_one)):
                scale = max(1.0, float(jnp.abs(b).max()))
                d = float(jnp.abs(a - b).max())
                assert d < 1e-4 * scale, (name, pa, d)
            # and against the reference oracle
            for a, b in zip(jax.tree.leaves(g_m), jax.tree.leaves(g_ref)):
                scale = max(1.0, float(jnp.abs(b).max()))
                assert float(jnp.abs(a - b).max()) < 2e-3 * scale
            print("OK", name)
        print("DONE")
        """ % hkv)
    assert "DONE" in out


@pytest.mark.slow
def test_multi_device_chunk_prefill_and_decode_parity():
    """Cache-level chunk prefill (per-row offsets) and decode under every
    mesh == the single-device fused step == the reference step, GQA."""
    out = run_py(_COMMON + """
        from repro.core import cache as cache_lib
        from repro.parallel.plan import AttentionPlan, as_plan

        B, S, H, Hkv, Dh, c, r = 4, 32, 4, 2, 8, 8, 2
        P_chunk, max_seq = 16, 64
        ks = jax.random.split(jax.random.PRNGKey(3), 6)
        q = jax.random.normal(ks[0], (B, P_chunk, H, Dh))
        k = jax.random.normal(ks[1], (B, P_chunk, Hkv, Dh))
        v = jax.random.normal(ks[2], (B, P_chunk, Hkv, Dh))
        E = jax.random.normal(ks[3], (c, r)) * 0.3
        F = jax.random.normal(ks[4], (c, r)) * 0.3
        M_ = (max_seq // c) * r
        lc = {
            "raw_k": jnp.zeros((B, c, Hkv, Dh)),
            "raw_v": jnp.zeros((B, c, Hkv, Dh)),
            "comp_k": jax.random.normal(ks[5], (B, M_, Hkv, Dh)) * 0.1,
            "comp_v": jax.random.normal(ks[5], (B, M_, Hkv, Dh)) * 0.1,
        }
        t0 = jnp.asarray([0, 8, 16, 24], jnp.int32)   # per-row offsets

        def stepped(step, plan):
            # the step, with the slots it writes applied to its cache
            def run(*a):
                out, writes = step(*a, plan=plan)
                return out, cache_lib.write_cache(a[3], writes)
            return run

        prefill = lambda plan: stepped(cache_lib.compressed_prefill_chunk,
                                       plan)
        decode = lambda plan: stepped(cache_lib.compressed_decode_attention,
                                      plan)
        o_ref, c_ref = prefill("reference")(q, k, v, lc, E, F, t0)
        o_one, c_one = prefill("fused")(q, k, v, lc, E, F, t0)
        np.testing.assert_allclose(o_one, o_ref, atol=1e-4, rtol=1e-4)

        # decode single-device baselines
        qd = q[:, :1]
        kd = k[:, :1]
        vd = v[:, :1]
        td = jnp.asarray([3, 7, 12, 20], jnp.int32)
        do_ref, dc_ref = decode("reference")(qd, kd, vd, lc, E, F, td)
        do_one, dc_one = decode("fused")(qd, kd, vd, lc, E, F, td)
        np.testing.assert_allclose(do_one, do_ref, atol=1e-4, rtol=1e-4)

        for name, (ms, ss) in MESHES.items():
            mesh = make_local_mesh(model_shards=ms, seq_shards=ss)
            ctx = ParallelCtx(mesh=mesh)
            plan = resolve_attention_plan(
                cfg_(Hkv).attention, ctx)
            with mesh:
                o_m, c_m = jax.jit(prefill(plan))(q, k, v, lc, E, F, t0)
                do_m, dc_m = jax.jit(decode(plan))(qd, kd, vd, lc, E, F, td)
            np.testing.assert_allclose(np.asarray(o_m), np.asarray(o_one),
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(do_m), np.asarray(do_one),
                                       atol=1e-5, rtol=1e-5)
            for key in c_one:
                np.testing.assert_allclose(
                    np.asarray(c_m[key]), np.asarray(c_one[key]),
                    atol=1e-5, rtol=1e-5, err_msg=(name, key))
                np.testing.assert_allclose(
                    np.asarray(dc_m[key]), np.asarray(dc_one[key]),
                    atol=1e-5, rtol=1e-5, err_msg=(name, key))
            print("OK", name)
        print("DONE")
        """)
    assert "DONE" in out


@pytest.mark.slow
def test_multi_device_exact_linformer_parity():
    """Exact (bidirectional) form: fwd + grads under tp×sp — the fused
    sequence-projection psum path — match the single-device fused run."""
    out = run_py(_COMMON + """
        def ecfg(backend):
            return ModelConfig(
                name="plan-exact", num_layers=2, d_model=32, vocab_size=256,
                max_seq_len=64, objective="mlm",
                attention=AttentionConfig(
                    kind="linformer", num_heads=4, num_kv_heads=2,
                    head_dim=8, causal=False, use_rope=False,
                    backend=backend,
                    linformer=LinformerConfig(k=16, sharing="layerwise")),
                dtype="float32", remat="none")

        cfg = ecfg("fused")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, 256)
        batch = {"tokens": toks, "labels": toks,
                 "loss_mask": jnp.ones((4, 64), jnp.int32)}

        def grads_for(cfg, ctx=None):
            fn = jax.jit(jax.value_and_grad(
                lambda p: M.loss_fn(p, cfg, batch, ctx=ctx)[0]))
            loss, g = fn(params)
            return float(loss), g

        l_ref, g_ref = grads_for(ecfg("reference"))
        l_one, g_one = grads_for(cfg)
        assert abs(l_ref - l_one) < 1e-4

        mesh = make_local_mesh(model_shards=2, seq_shards=2)
        ctx = ParallelCtx(mesh=mesh)
        with mesh:
            l_m, g_m = grads_for(cfg, ctx=ctx)
        assert abs(l_m - l_one) < 1e-5, (l_m, l_one)
        for a, b in zip(jax.tree.leaves(g_m), jax.tree.leaves(g_one)):
            scale = max(1.0, float(jnp.abs(b).max()))
            assert float(jnp.abs(a - b).max()) < 1e-4 * scale
        print("DONE")
        """)
    assert "DONE" in out


@pytest.mark.slow
def test_serving_engine_chunked_prefill_on_tp_mesh():
    """End-to-end serving (chunked admission prefill + continuous decode)
    on a tp=2 mesh is byte-identical to the single-device engine — the
    sharded pool cache (per-shard slots) changes nothing observable."""
    out = run_py(_COMMON + """
        from repro.serving.engine import ServingEngine
        cfg = cfg_(2)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[5, 6, 7] * 6, [9, 10] * 8, [3] * 21, [8] * 4]
        one = ServingEngine(params, cfg, max_seq=64, decode_chunk=4,
                            prefill_chunk=16)
        out1 = one.serve(prompts, 6, max_batch=2)
        mesh = make_local_mesh(model_shards=2)
        ctx = ParallelCtx(mesh=mesh)
        with mesh:
            two = ServingEngine(params, cfg, max_seq=64, ctx=ctx,
                                decode_chunk=4, prefill_chunk=16)
            assert two.plan.tp == 2
            # the pool cache really is sharded: per-shard slots on Hkv
            pool = two.init_pool_cache(2)
            spec = pool["comp_k"].sharding.spec
            assert spec[-2] == "model", spec
            out2 = two.serve(prompts, 6, max_batch=2)
        assert out1 == out2, (out1, out2)
        print("DONE")
        """)
    assert "DONE" in out


@pytest.mark.slow
def test_serving_snapshot_roundtrip_on_tp_mesh():
    """Preemption on a tp=2 mesh: slot snapshots gather from the SHARDED
    pool cache, restores scatter back into it, and the whole
    preempt -> requeue -> resume cycle is byte-identical to the
    single-device engine. After restores the pool must still carry the
    plan's layout (per-shard slots on the KV-head axis) — snapshot
    round-trips preserve sharding exactly as donation does."""
    out = run_py(_COMMON + """
        from repro.serving.engine import ServingEngine
        from repro.serving.faults import Fault, FaultInjector
        cfg = cfg_(2)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[5, 6, 7] * 6, [9, 10] * 8, [3] * 21, [8] * 4,
                   [11, 4] * 5, [2, 3, 4] * 4]
        budgets = [16, 16, 16, 6, 6, 6]   # low-pri long, hi-pri short
        kw = dict(max_batch=2, priorities=[3, 3, 3, 0, 0, 0],
                  arrival_chunks=[0, 0, 0, 1, 1, 2],
                  return_scheduler=True)
        one = ServingEngine(params, cfg, max_seq=64, decode_chunk=4,
                            prefill_chunk=16)
        out1, s1 = one.serve(prompts, budgets, **kw)
        assert s1.stats.preemptions > 0, s1.stats
        mesh = make_local_mesh(model_shards=2)
        ctx = ParallelCtx(mesh=mesh)
        with mesh:
            two = ServingEngine(params, cfg, max_seq=64, ctx=ctx,
                                decode_chunk=4, prefill_chunk=16)
            out2, s2 = two.serve(prompts, budgets, **kw)
            assert s2.stats.preemptions == s1.stats.preemptions
            # a fault-recovery restore also round-trips the sharded pool
            inj = FaultInjector([Fault("slot_step", chunk=1, row=0)])
            out3, s3 = two.serve(prompts, budgets, max_batch=2,
                                 snapshot_chunks=1, fault_injector=inj,
                                 return_scheduler=True)
            assert s3.stats.quarantines == 1
            # primitive-level: gather -> host -> scatter round-trips the
            # sharded pool byte-exactly AND restores the plan's layout
            pool = two.init_pool_cache(2)
            spec0 = pool["comp_k"].sharding.spec
            assert spec0[-2] == "model", spec0
            snap = two.snapshot_pool_rows(pool, [0, 1], pad_to=2)
            pool = two.restore_pool_rows(
                pool, {k: jnp.asarray(v) for k, v in snap[0].items()}, 0)
            assert pool["comp_k"].sharding.spec == spec0, \\
                pool["comp_k"].sharding.spec
            back = two.snapshot_pool_rows(pool, [0, 1], pad_to=2)
            for a, b in zip(snap, back):
                for key in a:
                    np.testing.assert_array_equal(a[key], b[key])
        assert out1 == out2, (out1, out2)
        plain = one.serve(prompts, budgets, max_batch=2)
        assert out3 == plain, (out3, plain)
        print("DONE")
        """)
    assert "DONE" in out


@pytest.mark.slow
def test_paged_pool_snapshot_roundtrip_on_tp_mesh():
    """Paged, quantized pool on a tp=2 mesh: the page arena is sharded over
    the KV-head axis (scale leaves on their LAST axis), preempt/restore
    through quantized snapshots is byte-identical to the single-device
    paged engine, and a primitive-level snapshot -> restore-into-fresh-pages
    round-trip preserves both the bytes and the plan's layout."""
    out = run_py(_COMMON + """
        from repro.serving import Request
        from repro.serving.engine import ServingEngine
        from repro.serving.scheduler import SlotPool
        cfg = cfg_(2)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[5, 6, 7] * 6, [9, 10] * 8, [3] * 21, [8] * 4,
                   [11, 4] * 5, [2, 3, 4] * 4]
        budgets = [16, 16, 16, 6, 6, 6]   # low-pri long, hi-pri short
        kw = dict(max_batch=2, priorities=[3, 3, 3, 0, 0, 0],
                  arrival_chunks=[0, 0, 0, 1, 1, 2],
                  snapshot_chunks=2, return_scheduler=True)
        mk = lambda ctx=None, pc=16: ServingEngine(
            params, cfg, max_seq=64, ctx=ctx, decode_chunk=4,
            prefill_chunk=pc, cache_format="paged")
        one = mk()
        out1, s1 = one.serve(prompts, budgets, **kw)
        assert s1.stats.preemptions > 0, s1.stats   # restores exercised
        mesh = make_local_mesh(model_shards=2)
        ctx = ParallelCtx(mesh=mesh)
        with mesh:
            two = mk(ctx)
            assert two.plan.tp == 2
            pool = two.init_pool_cache(2)
            # the arena is genuinely sharded: payloads on the Hkv axis
            # (nd-2), per-page scales on THEIR Hkv axis (last)
            assert pool["page_k"].sharding.spec[-2] == "model"
            assert pool["page_k_s"].sharding.spec[-1] == "model"
            assert pool["raw_k_s"].sharding.spec[-1] == "model"
            out2, s2 = two.serve(prompts, budgets, **kw)
            assert s2.stats.preemptions == s1.stats.preemptions
            # primitive-level: admit one row, snapshot it, restore into
            # FRESH pages on another row — bytes and layout both survive.
            # (monolithic admission requires prefill_chunk=0: the external
            # prefill's slot count must equal the arena fold maxp*r)
            two0 = mk(ctx, pc=0)
            sp = SlotPool(two0, 2)
            spec0 = sp.cache["page_k"].sharding.spec
            prompt = [5, 6, 7] * 6
            cache, logits = two0.prefill(np.asarray([prompt], np.int32))
            req = Request(rid=0, tokens=tuple(prompt), max_new_tokens=4)
            sp.admit(0, req, cache, int(jnp.argmax(logits[0])))
            snap = sp.snapshot_rows([0], tick=0)[0]
            assert snap.verify()
            sp.restore(1, req, snap)
            assert sp.cache["page_k"].sharding.spec == spec0
            back = sp.snapshot_rows([1], tick=0)[0]
            for key in snap.cache_rows:
                np.testing.assert_array_equal(snap.cache_rows[key],
                                              back.cache_rows[key], key)
        assert out1 == out2, (out1, out2)
        print("DONE")
        """)
    assert "DONE" in out


@pytest.mark.slow
def test_mesh_validation_indivisible_hkv():
    """tp that does not divide Hkv: strict validation raises the clear
    launch/mesh.py error; plan resolution warns and demotes attention to
    the unsharded-fused path (the model axis is shared with expert
    parallelism, so e.g. MoE's 4-wide expert axis over Hkv=2 must keep
    working — test_distributed.py::test_tiny_mesh_train_step covers the
    full model)."""
    out = run_py(_COMMON + """
        import warnings
        from repro.launch.mesh import validate_attention_mesh
        mesh = make_local_mesh(model_shards=8)     # tp=8, Hkv=2
        ctx = ParallelCtx(mesh=mesh)
        try:
            validate_attention_mesh(mesh, num_heads=4, num_kv_heads=2,
                                    strict=True)
        except ValueError as e:
            assert "does not divide num_kv_heads" in str(e), e
        else:
            raise AssertionError("expected strict ValueError")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            plan = resolve_attention_plan(cfg_(2).attention, ctx)
        assert any("does not divide num_kv_heads" in str(x.message)
                   for x in w), [str(x.message) for x in w]
        assert plan.tp_axis is None and not plan.manual
        print("DONE")
        """)
    assert "DONE" in out


@pytest.mark.slow
def test_sp_train_fails_fast_on_indivisible_seq():
    """An S that cannot hold whole blocks per sp shard raises the clear
    validate_seq_shards error from inside the training path."""
    out = run_py(_COMMON + """
        cfg = cfg_(2)
        mesh = make_local_mesh(seq_shards=4)       # S=24 -> 3 blocks, sp=4
        ctx = ParallelCtx(mesh=mesh)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 24), 0, 256)
        batch = {"tokens": toks, "labels": toks,
                 "loss_mask": jnp.ones((4, 24), jnp.int32)}
        try:
            with mesh:
                jax.jit(lambda p: M.loss_fn(p, cfg, batch, ctx=ctx)[0])(
                    params)
        except ValueError as e:
            assert "whole number of 8-token attention blocks" in str(e), e
            print("DONE")
        else:
            raise AssertionError("expected fail-fast ValueError")
        """)
    assert "DONE" in out
