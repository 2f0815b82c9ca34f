"""The serving runner's programs on four chips, compiled for a described TPU
v5e 2x2 host: the whole 36-layer qwen3-8b (bench/configs/qwen3-8b-36L-tp4)
at tp=4 under the `serve-mixed` mix, as bench/cells/serve.py builds its
engine on a mesh (16-row pool, max_seq 32768, prefill chunk 512, decode
chunk 8). Nothing runs: the TPU compiler refuses a program that does not
fit a chip, or a kernel that cannot be partitioned, and each program's
bytes per chip are read from `memory_analysis()`.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every pytest worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CONFIG, MIX = "qwen3-8b-36L-tp4", "serve-mixed"
HBM_BYTES = 16e9        # one v5e chip


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh
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
    yield Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
               axis_types=(jax.sharding.AxisType.Auto,) * 2)
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def cell_programs(mesh):
    """The engine's decode-chunk, prefill-chunk and remainder programs with
    their arguments as shapes laid out on the described mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import harness
    from bench.cells import serve
    from repro.kernels import ops
    from repro.models import model as M
    from repro.parallel.sharding import ParallelCtx
    cell = harness.Cell(name=f"{CONFIG}.{MIX}", chips=4,
                        config=harness._json("configs", f"{CONFIG}.json"),
                        mix=harness._json("traffic", f"{MIX}.json"),
                        limits={}, per_layer=[])
    mix = cell.mix
    ctx = ParallelCtx(mesh=mesh)
    mc = harness.model_config(cell.config)
    shapes, layout = serve._shapes_and_layout(mc, ctx)
    spec = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    params = jax.tree.map(spec, shapes, layout)
    rows = mix["pool_rows"]
    real = ops._auto_interpret
    ops._auto_interpret = lambda interpret: bool(interpret)   # Mosaic
    try:
        with mesh:
            eng = serve._engine(cell, params, mc, ctx)
            cache = jax.eval_shape(lambda: M.init_cache(
                mc, batch=rows, max_seq=mix["max_seq"] + mix["prefill_chunk"],
                dtype=jnp.bfloat16))
            pool = jax.tree.map(spec, cache, eng.plan.cache_shardings(cache))
            one = NamedSharding(mesh, P())
            arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=one)
            progs = {
                "decode_chunk": eng.pool_chunk_fn(mix["decode_chunk"]).lower(
                    params, arg((rows,), jnp.int32), arg((rows,), jnp.bool_),
                    pool, arg((2,), jnp.uint32)),
                "prefill_chunk": eng._pool_prefill_chunk.lower(
                    params, pool, arg((rows, mix["prefill_chunk"]), jnp.int32),
                    arg((rows,), jnp.int32), arg((rows,), jnp.int32)),
                "prefill_remainder": eng._pool_prefill_remainder.lower(
                    params, pool,
                    arg((rows, max(mix["prompt"]["remainders"])), jnp.int32),
                    arg((rows,), jnp.int32)),
            }
            yield {k: v.compile() for k, v in progs.items()}
    finally:
        ops._auto_interpret = real


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_chunk",
                                     "prefill_remainder"])
def test_cell_program_compiles_for_four_v5e_chips(cell_programs, program):
    compiled = cell_programs[program]
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo            # the fused attention kernel
    assert "all-reduce" in hlo                 # tensor parallelism
    mem = compiled.memory_analysis()
    per_chip = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{program}: arguments {mem.argument_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes}, outputs {mem.output_size_in_bytes} "
          f"(aliased {mem.alias_size_in_bytes}) bytes per chip")
    assert per_chip < HBM_BYTES
