"""Multi-device tests (subprocess with 8 host devices): sharded == local for
the MoE shard_map, sharding rules, tiny-mesh lower+compile, and the HLO cost
analyzer on a real partitioned module.

These run in subprocesses because the main test process must keep 1 device.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_moe_shard_map_matches_local():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import MLPConfig, MoEConfig
        from repro.models.moe import apply_moe, init_moe
        from repro.parallel.sharding import ParallelCtx
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(model_shards=4)   # 2 data x 4 model
        cfg = MoEConfig(num_experts=8, top_k=2, expert_d_ff=32,
                        capacity_factor=8.0)
        mlp = MLPConfig(activation="swiglu")
        p = init_moe(jax.random.PRNGKey(0), 16, cfg, mlp, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))
        local, aux_l = apply_moe(p, x, cfg, mlp, None)
        ctx = ParallelCtx(mesh=mesh)
        with mesh:
            sharded, aux_s = jax.jit(
                lambda pp, xx: apply_moe(pp, xx, cfg, mlp, ctx))(p, x)
        err = float(jnp.abs(local - sharded).max())
        print("ERR", err)
        # capacity is computed from LOCAL token counts (T/2 per shard) so
        # with generous capacity_factor routing is identical
        assert err < 1e-4, err
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_weight_stationary_decode_matches_local():
    """§Perf iteration (kimi decode): weights stay sharded, tokens move."""
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs.base import MLPConfig, MoEConfig
        from repro.models.moe import apply_moe, init_moe
        from repro.parallel.sharding import ParallelCtx
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(model_shards=4)
        cfg = MoEConfig(num_experts=8, top_k=2, expert_d_ff=32,
                        capacity_factor=8.0, weight_stationary_decode=True,
                        capacity_floor_one=True)
        mlp = MLPConfig(activation="swiglu")
        p = init_moe(jax.random.PRNGKey(0), 16, cfg, mlp, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (6, 1, 16))
        local, _ = apply_moe(p, x, dataclasses.replace(
            cfg, weight_stationary_decode=False), mlp, None)
        ctx = ParallelCtx(mesh=mesh, fsdp="data")
        with mesh:
            ws, _ = jax.jit(lambda pp, xx: apply_moe(pp, xx, cfg, mlp,
                                                     ctx))(p, x)
        err = float(jnp.abs(local - ws).max())
        assert err < 1e-4, err
        print("OK", err)
        """)
    assert "OK" in out


@pytest.mark.slow
def test_tiny_mesh_train_step_compiles_with_shardings():
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.configs.base import OptimizerConfig
        from repro.launch.mesh import make_local_mesh
        from repro.models import model as M
        from repro.optim import adamw_init
        from repro.parallel.sharding import ParallelCtx, param_shardings
        from repro.train.trainer import make_train_step

        cfg = dataclasses.replace(get_smoke_config("qwen3-moe-30b-a3b"),
                                  dtype="float32")
        mesh = make_local_mesh(model_shards=4)
        ctx = ParallelCtx(mesh=mesh, fsdp="data")
        params_abs = jax.eval_shape(
            lambda: M.init_params(jax.random.PRNGKey(0), cfg))
        p_sh = param_shardings(params_abs, ctx)
        opt_abs = jax.eval_shape(
            lambda: adamw_init(params_abs, OptimizerConfig()))
        from jax.sharding import NamedSharding, PartitionSpec as P
        o_sh = {"mu": param_shardings(opt_abs["mu"], ctx),
                "nu": param_shardings(opt_abs["nu"], ctx),
                "step": NamedSharding(mesh, P())}
        batch = {
            "tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
            "labels": jax.ShapeDtypeStruct((4, 32), jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct((4, 32), jnp.int32),
        }
        b_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        step = make_train_step(cfg, OptimizerConfig(), ctx=ctx)
        with mesh:
            lowered = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh)).lower(
                params_abs, opt_abs, batch)
            compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):   # older jax: one dict per device
            cost = cost[0]
        assert float(cost.get("flops", 0)) > 0
        print("OK flops", cost.get("flops"))
        """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_training_matches_single_device():
    """Numerical parity: DP+TP sharded train step == unsharded step."""
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.configs.base import OptimizerConfig
        from repro.launch.mesh import make_local_mesh
        from repro.models import model as M
        from repro.optim import adamw_init
        from repro.parallel.sharding import ParallelCtx, param_shardings
        from repro.train.trainer import make_train_step

        cfg = dataclasses.replace(get_smoke_config("qwen3-8b"),
                                  dtype="float32")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        ocfg = OptimizerConfig(lr=1e-3, warmup_steps=0)
        opt = adamw_init(params, ocfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 512)
        batch = {"tokens": toks, "labels": toks,
                 "loss_mask": jnp.ones((4, 32), jnp.int32)}

        ref_step = make_train_step(cfg, ocfg)
        p1, o1, m1 = jax.jit(ref_step)(params, opt, batch)

        mesh = make_local_mesh(model_shards=2)
        ctx = ParallelCtx(mesh=mesh, fsdp="data")
        step = make_train_step(cfg, ocfg, ctx=ctx)
        p_sh = param_shardings(params, ctx)
        with mesh:
            p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, None, None))(
                params, opt, batch)
        print("LOSS", float(m1["loss"]), float(m2["loss"]))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        d = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        print("MAXDIFF", d)
        assert d < 1e-4
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_seq_parallel_linformer_matches_exact():
    """Beyond-paper: sequence-parallel projection psums only (k x d)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.core.seq_parallel import seq_parallel_linformer_attention
        from repro.core import exact_linformer_attention
        from repro.parallel.sharding import ParallelCtx
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(model_shards=8)
        ctx = ParallelCtx(mesh=mesh)
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (2, 64, 4, 8))
        k = jax.random.normal(ks[1], (2, 64, 2, 8))
        v = jax.random.normal(ks[2], (2, 64, 2, 8))
        E = jax.random.normal(ks[3], (64, 16)) * 0.25
        F = jax.random.normal(ks[4], (64, 16)) * 0.25
        ref = exact_linformer_attention(q, k, v, E, F)
        with mesh:
            o = jax.jit(lambda *a: seq_parallel_linformer_attention(
                *a, ctx))(q, k, v, E, F)
        err = float(jnp.abs(o - ref).max())
        assert err < 1e-4, err
        print("OK", err)
        """)
    assert "OK" in out


@pytest.mark.slow
def test_hlo_cost_analyzer_counts_loop_collectives():
    """FSDP all-gathers inside a scanned layer loop must be multiplied by the
    trip count (the motivation for hlo_cost.py)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_cost import analyze_text
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        L, D = 7, 64

        def f(ws, x):
            def body(h, w):
                w = jax.lax.with_sharding_constraint(
                    w, NamedSharding(mesh, P(None, None)))
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, ws)
            return h.sum()

        ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
        x = jax.ShapeDtypeStruct((16, D), jnp.float32)
        sh = NamedSharding(mesh, P(None, "data", None))   # fsdp-style
        with mesh:
            c = jax.jit(f, in_shardings=(sh, NamedSharding(mesh, P()))
                        ).lower(ws, x).compile()
        a = analyze_text(c.as_text())
        ag = a["collectives"]["all-gather"]
        print("AG", ag)
        assert ag["count"] >= L   # one gather per layer iteration
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_cross_pod_gradients_track_exact():
    """EF-int8 cross-pod DP (train/compressed_dp.py): first step identical
    (quantization is absorbed by clip+Adam sign structure at step 1), later
    steps track exact training within quantization noise."""
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.configs.base import OptimizerConfig
        from repro.models import model as M
        from repro.optim import adamw_init
        from repro.parallel.sharding import ParallelCtx
        from repro.train.trainer import make_train_step
        from repro.train.compressed_dp import (make_compressed_train_step,
                                               init_residual)
        from repro.launch.mesh import make_mesh

        cfg = dataclasses.replace(get_smoke_config("qwen3-8b"),
                                  dtype="float32")
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        ctx = ParallelCtx(mesh=mesh, fsdp="data")
        ocfg = OptimizerConfig(lr=1e-3, warmup_steps=0)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params, ocfg)
        ref_step = jax.jit(make_train_step(cfg, ocfg))
        comp_step = jax.jit(make_compressed_train_step(cfg, ocfg, ctx))
        res = init_residual(params, 2)
        pe, oe, pc, oc = params, opt, params, opt
        for s in range(3):
            toks = jax.random.randint(jax.random.PRNGKey(s), (8, 32), 0,
                                      cfg.vocab_size)
            b = {"tokens": toks, "labels": toks,
                 "loss_mask": jnp.ones((8, 32), jnp.int32)}
            pe, oe, me = ref_step(pe, oe, b)
            with mesh:
                pc, oc, res, mc = comp_step(pc, oc, res, b)
            diff = abs(float(me["loss"]) - float(mc["loss"]))
            assert diff < 5e-3, (s, diff)
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_trainer_with_compressed_pod_grads_end_to_end():
    """TrainConfig.compressed_pod_grads: full loop incl. residual
    checkpointing + resume on a (pod,data,model) mesh."""
    out = run_py("""
        import dataclasses, tempfile, jax
        from repro.configs import get_smoke_config
        from repro.configs.base import OptimizerConfig, TrainConfig
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import ParallelCtx
        from repro.train import Trainer

        cfg = dataclasses.replace(get_smoke_config("qwen3-8b"),
                                  dtype="float32")
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        ctx = ParallelCtx(mesh=mesh, fsdp="none")
        d = tempfile.mkdtemp()
        tcfg = TrainConfig(seq_len=32, global_batch=8, steps=6, log_every=99,
                           checkpoint_every=3, checkpoint_dir=d,
                           compressed_pod_grads=True,
                           optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1,
                                                     total_steps=20))
        with mesh:
            m = Trainer(cfg, tcfg, log_fn=lambda s: None, ctx=ctx).run()
            tr2 = Trainer(cfg, dataclasses.replace(tcfg, steps=8),
                          log_fn=lambda s: None, ctx=ctx)
            p, o, ds, start = tr2.restore_or_init()
            assert start == 6, start
            m2 = tr2.run()
        assert m2["loss"] < 8.0
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_restart_trainer_on_mesh():
    """Checkpoint written single-device, resumed on an 8-device mesh with
    resharding — the elastic-restart path end to end."""
    out = run_py("""
        import dataclasses, tempfile, jax
        from repro.configs import get_smoke_config
        from repro.configs.base import OptimizerConfig, TrainConfig
        from repro.launch.mesh import make_local_mesh
        from repro.parallel.sharding import ParallelCtx
        from repro.train import Trainer

        cfg = dataclasses.replace(get_smoke_config("qwen3-8b"),
                                  dtype="float32")
        d = tempfile.mkdtemp()
        tcfg = TrainConfig(seq_len=32, global_batch=8, steps=4, log_every=99,
                          checkpoint_every=2, checkpoint_dir=d,
                          optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1,
                                                    total_steps=20))
        # phase 1: single-device "cluster"
        Trainer(cfg, tcfg, log_fn=lambda s: None).run()
        # phase 2: "grown" cluster — 8 devices, 2-way TP
        mesh = make_local_mesh(model_shards=2)
        ctx = ParallelCtx(mesh=mesh, fsdp="data")
        tcfg2 = dataclasses.replace(tcfg, steps=6)
        with mesh:
            tr = Trainer(cfg, tcfg2, ctx=ctx, log_fn=lambda s: None)
            params, opt, ds, start = tr.restore_or_init()
            assert start == 4, start
            # params actually sharded on the new mesh
            shardings = {str(x.sharding) for x in jax.tree.leaves(params)}
            assert any("model" in s for s in shardings), shardings
            m = tr.run()
        assert m["loss"] > 0
        print("OK")
        """)
    assert "OK" in out


def test_param_sharding_rules():
    """Path-based rules produce the documented PartitionSpecs."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import spec_for_path
    assert spec_for_path("layers/attn/wq", ("data",), 3) == \
        P(None, "data", "model")
    assert spec_for_path("layers/attn/wo", ("data",), 3) == \
        P(None, "model", "data")
    assert spec_for_path("layers/moe/w_in", ("data",), 4) == \
        P(None, "model", "data", None)
    assert spec_for_path("embed/tok", (), 2) == P("model", None)
    assert spec_for_path("lm_head", ("pod", "data"), 2) == \
        P(("pod", "data"), "model")
    # shared zamba block: rank-2 (no layer axis)
    assert spec_for_path("shared_block/attn/wq", (), 2) == P(None, "model")
    # linformer E/F replicated
    assert spec_for_path("shared/lin/E", ("data",), 2) == P(None, None)
    # rwkv
    assert spec_for_path("layers/rwkv/w_r", ("data",), 3) == \
        P(None, "data", "model")
