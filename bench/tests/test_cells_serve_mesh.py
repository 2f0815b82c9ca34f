"""The serving cell on several chips, rehearsed on the CPU: sharded weights
are the unsharded draw, bit for bit; the runner drives a four-device mesh
end to end (subprocesses with four forced host devices); the trace
reduction counts each device's programs and the collectives' time."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_py(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    return out.stdout


def test_sharded_weights_are_the_unsharded_draw():
    out = run_py("""
        import json
        import jax
        import numpy as np
        from bench import harness, weights
        from bench.cells import serve
        from bench.tests import cells_smoke as cs
        cell = cs.serve_cell()
        cell.chips = 4
        ctx = serve._parallel(cell)
        mc = harness.model_config(cell.config)
        shapes, layout = serve._shapes_and_layout(mc, ctx)
        one = weights.make(shapes, 2**31 + 12345, 500)
        four = weights.make(shapes, 2**31 + 12345, 500, layout)
        same = jax.tree.map(lambda a, b: bool(np.array_equal(
            np.asarray(a), np.asarray(b))), one, four)
        wq = four["layers"]["attn"]["wq"]
        print(json.dumps({
            "same": all(jax.tree.leaves(same)),
            "devices": len(wq.sharding.device_set),
            "shard": list(wq.addressable_shards[0].data.shape),
            "whole": list(wq.shape)}))
    """)
    r = json.loads(out.splitlines()[-1])
    assert r["same"]
    assert r["devices"] == 4
    assert r["shard"][-1] * 4 == r["whole"][-1]


def test_serve_runner_drives_a_four_device_mesh():
    """The SMOKE model with 4 KV heads (the registry's has 2), so that tp=4
    head-shards attention as 8 KV heads over 4 chips do at full size."""
    out = run_py("""
        import dataclasses, json, time
        from bench import harness
        from bench.cells import serve
        from bench.tests import cells_smoke as cs
        real = harness.model_config

        def four_kv(cfg):
            mc = real(dict(cfg, num_key_value_heads=2))
            return dataclasses.replace(mc, attention=dataclasses.replace(
                mc.attention, num_kv_heads=4))

        harness.model_config = four_kv
        cell = cs.serve_cell(config=dict(cs.QWEN_SMOKE,
                                         num_key_value_heads=4))
        cell.chips = 4
        out = serve.run(cell, 3_000_000_007, 1.5, False, cs.DEVICE,
                        time.perf_counter())
        eng_tp = serve._parallel(cell).mesh.shape["model"]
        print(json.dumps(harness.plain(dict(out, tp=eng_tp))))
    """)
    r = json.loads(out.splitlines()[-1])
    assert r["tp"] == 4
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 10
    assert r["notes"]["compiles_in_window"] == 0
    assert r["metrics"]["tpot_p90_ms"]["value"] > 0


def _two_devices() -> tr.Trace:
    return tr.Trace(
        window=(0.0, 10.0),
        ops={"/device:TPU:0": [("while", 0.0, 6.0), ("fusion", 0.0, 2.0),
                               ("all-reduce", 2.0, 3.0),
                               ("fusion", 2.5, 2.7),
                               ("all-gather-done", 5.0, 6.0),
                               ("fusion", 5.5, 7.0)],
             "/device:TPU:1": [("fusion", 0.0, 2.0),
                               ("all-reduce", 2.0, 3.5)]},
        modules={"/device:TPU:0": [("jit__lambda", 0.0, 3.0),
                                   ("jit__lambda", 5.0, 7.0)],
                 "/device:TPU:1": [("jit__lambda", 0.0, 3.5)]})


def test_reduce_counts_each_devices_programs_and_the_collectives():
    r = tr.reduce(_two_devices())
    assert r["module_n"] == {"jit__lambda": 3}
    assert r["module_n_by_device"] == {"/device:TPU:0": {"jit__lambda": 2},
                                       "/device:TPU:1": {"jit__lambda": 1}}
    assert r["collective_s"] == pytest.approx(1.0 + 1.0 + 1.5)
    # device 0: 0.2 s of its all-reduce and 0.5 s of its all-gather run
    # beside a fusion (the `while` around them hides nothing)
    assert r["collective_exposed_s"] == pytest.approx(3.5 - 0.2 - 0.5)
    assert r["op_s"]["all-reduce"] == pytest.approx(2.5)


def test_one_device_has_no_collectives_to_read():
    from bench import harness
    r = tr.reduce(tr.Trace(window=(0.0, 1.0),
                           ops={"/device:TPU:0": [("fusion", 0.1, 0.5)]},
                           modules={"/device:TPU:0": [("jit__lambda", 0.1,
                                                       0.5)]}))
    assert r["collective_s"] == 0.0 and r["collective_exposed_s"] == 0.0
    for m in ("collective_share.serve", "collective_exposed.serve"):
        assert harness.load_reader(m)({"trace": r}) is None


def test_collective_readers():
    from bench import harness
    r = tr.reduce(_two_devices())
    share = harness.load_reader("collective_share.serve")({"trace": r})
    exposed = harness.load_reader("collective_exposed.serve")({"trace": r})
    assert share == pytest.approx(100.0 * 3.5 / 8.5)
    assert exposed == pytest.approx(100.0 * 2.8 / 3.5)


def test_every_chip_has_to_run_each_decode_chunk():
    from bench.cells import serve
    red = tr.reduce(_two_devices())
    with pytest.raises(serve.WorkMismatch):
        serve._decode_runs_agree(red, 2, 2)        # device 1 ran one
    red["module_n_by_device"]["/device:TPU:1"]["jit__lambda"] = 2
    serve._decode_runs_agree(red, 2, 2)
    with pytest.raises(serve.WorkMismatch):
        serve._decode_runs_agree(red, 2, 4)        # two chips ran nothing
