"""Compiles of the device paths for a described TPU v5e (no chip needed).

The TPU compiler is installed with jax, and it compiles for a chip that is
described and not attached: these tests refuse what the chip's compiler
would refuse (an op Mosaic cannot lower, a program that does not fit the
chip's 16 GiB) without running anything.  The topology is described inside
a module-scoped fixture, never at import: only one process at a time may
load the TPU library, and each test worker imports every test file.
"""

import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("impl,c", [("sequential", 1), ("associative", 1),
                                    ("sequential", 4)])
def test_fastsim_sweep_compiles_f64(one_chip, impl, c):
    """The fast-path sweep at float64 on a 256 x 32768 grid, the engine
    behind ``Planner.validate`` and ``simulate_batch(backend="jax")``."""
    from repro.serving import fastsim

    b, n = 256, 32768
    with jax.enable_x64(True):
        args = (_sds((b, n), jnp.float64, one_chip),
                _sds((b, n), jnp.float64, one_chip),
                _sds((b,), jnp.int64, one_chip),
                _sds((), jnp.float64, one_chip))
        compiled = fastsim._jax_sweep.lower(
            *args, impl=impl, c=c, has_slo=True).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < V5E_HBM_BYTES


def test_replay_dag_chunk_compiles_f64(one_chip):
    """The streamed DAG replay's chunk evaluator at float64 for the
    3-stage RAG pipeline over 2**18 arrival slots, the largest padded
    chunk of a day of the diurnal trace.  Its prefixes are explicit
    associative scans: ``cumsum`` lowers on the TPU to a float64
    ``reduce_window`` whose compile takes minutes at this size."""
    from repro.serving import traces

    j, n = 3, 1 << 18
    with jax.enable_x64(True):
        chunk = traces._make_chunk_dag_jax(j)
        chunk.lower(_sds((n,), jnp.float64, one_chip),
                    _sds((j, n), jnp.float64, one_chip),
                    _sds((j,), jnp.float64, one_chip)).compile()


@pytest.mark.parametrize("chained", [False, True])
def test_lindley_kernels_lower_for_tpu(one_chip, chained):
    """The blocked Pallas Lindley kernels at float32 with interpret=False
    compile to a Mosaic custom call (interpret mode would hide a lowering
    the chip refuses)."""
    from repro.kernels.lindley_scan import kernel

    n, b = 4096, 512
    a = _sds((n, b), jnp.float32, one_chip)
    if chained:
        s = _sds((3, n, b), jnp.float32, one_chip)
        fn = jax.jit(lambda a, s: kernel.chained_lindley_scan(
            a, s, interpret=False))
    else:
        s = _sds((n, b), jnp.float32, one_chip)
        fn = jax.jit(lambda a, s: kernel.lindley_scan(a, s, interpret=False))
    compiled = fn.lower(a, s).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_fits_one_chip(topo):
    """The accurate rung's decode step of internlm2-1.8b at full width
    (batch 8, prompt 512, 32 new tokens), as ``repro.launch.serve``
    builds it on a 1 x 1 mesh: arguments plus temporaries fit one v5e."""
    import repro.configs  # noqa: F401
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import rung_programs
    from repro.models.registry import build_model, get_config
    from repro.sharding.planner import ShardingPlanner

    cfg = get_config("internlm2-1.8b")
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (24, 2048, 92544)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    planner = ShardingPlanner(mesh, fsdp=False, context="serve")
    with jax.set_mesh(mesh):
        jitted, args = rung_programs(build_model(cfg), planner, batch=8,
                                     prompt_len=512, max_new=32)["decode"]
        compiled = jitted.lower(*args).compile()
    mem = compiled.memory_analysis()
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(args[0]))
    assert mem.argument_size_in_bytes >= param_bytes
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used / 2**30


def test_moe_share_decode_step_fits_one_chip(topo):
    """The accurate rung's decode step of one expert-parallel rank's share
    of deepseek-moe-16b (14 of 28 layers, experts 0-7 of 64, batch 64,
    prompt 128, 64 new tokens): the grouped dispatch's ``ragged_dot``
    lowers to a TPU kernel, and the step, with the other rung's cache,
    fits one v5e."""
    import dataclasses

    import repro.configs  # noqa: F401
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import rung_configs, rung_programs
    from repro.models.registry import build_model, get_config
    from repro.sharding.planner import ShardingPlanner

    cfg = dataclasses.replace(get_config("deepseek-moe-16b"), num_layers=14,
                              experts_held=8)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    planner = ShardingPlanner(mesh, fsdp=False, context="serve")
    rungs = rung_configs(cfg, 256)
    with jax.set_mesh(mesh):
        jitted, args = rung_programs(build_model(rungs["accurate"]), planner,
                                     batch=64, prompt_len=128,
                                     max_new=64)["decode"]
        compiled = jitted.lower(*args).compile()
        fast_state = rung_programs(build_model(rungs["fast"]), planner,
                                   batch=64, prompt_len=128,
                                   max_new=64)["decode"][1][1]
    assert "ragged-dot" in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    other = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(fast_state))
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes + other)
    assert used < 0.9 * V5E_HBM_BYTES, used / 2**30
