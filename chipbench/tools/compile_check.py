"""Compile a cell's programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 chipbench/tools/compile_check.py \\
        internlm2-1.8b.rag stablelm-3b.rag

For each cell: the serving plane's five programs (init, and prefill and
decode of each rung) as ``ServingPlane`` builds them, and the reference's
layer and head at the check's block of rows; prints each program's
``memory_analysis`` (arguments, outputs, temporaries) in GiB.  Nothing
runs, so nothing here is a time; a program that does not fit the chip's
16 GiB, or that its compiler refuses, fails here as it would there.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
GIB = 2.0 ** 30


def mem(name, compiled) -> None:
    m = compiled.memory_analysis()
    print(f"  {name:<18} args {m.argument_size_in_bytes / GIB:7.3f}  "
          f"out {m.output_size_in_bytes / GIB:7.3f}  "
          f"temp {m.temp_size_in_bytes / GIB:7.3f}  "
          f"(alias {m.alias_size_in_bytes / GIB:.3f}) GiB", flush=True)


def main(cells) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import check, families, spec, weights
    from repro.launch import serve
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.sharding.planner import ShardingPlanner

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    for name in cells:
        cell = spec.load_cell(name)
        t = cell.traffic
        b, p, n = int(t["batch"]), int(t["prompt_len"]), int(t["new_tokens"])
        arch = families.arch(cell.config)
        family = sys.modules[type(arch).__module__]   # its layer and head
        cfg = arch.program_config(cell.config)
        serving = cell.config["serving"]
        print(f"{name}: batch {b}, prompt {p}, {n} new tokens", flush=True)
        mesh = make_mesh([1, 1], ("data", "model"), devices=[dev])
        planner = ShardingPlanner(mesh, fsdp=False, context="serve")
        models = {k: build_model(c) for k, c in serve.rung_configs(
            cfg, int(serving["fast"]["sliding_window"])).items()}
        with jax.set_mesh(mesh):
            acc = models["accurate"]
            key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                       sharding=planner.replicated())
            mem("init", jax.jit(acc.init, out_shardings=planner.param_shardings(
                acc)).lower(key).compile())
            for rung, m in models.items():
                progs = serve.rung_programs(m, planner, batch=b,
                                            prompt_len=p, max_new=n)
                for kind in ("prefill", "decode"):
                    jitted, args = progs[kind]
                    mem(f"{kind}_{rung}", jitted.lower(*args).compile())
        w = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one),
            jax.eval_shape(lambda: weights.make(arch, 0)))
        rows = min(4, b)
        s_len = p + n
        x = jax.ShapeDtypeStruct((rows, s_len, arch.d), jnp.float32,
                                 sharding=one)
        i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        for rung in ("accurate", "fast"):
            kw = check.rung_reference(serving, rung, p)
            mem(f"ref_layer_{rung}", family.layer.lower(
                x, w["decoder"][0], i, eps=arch.norm_eps,
                theta=arch.rope_theta, operand=None, **kw).compile())
        tail = jax.ShapeDtypeStruct((rows, n + 1, arch.d), jnp.float32,
                                    sharding=one)
        mem("ref_head", family.head.lower(
            tail, w["embed"]["final_norm"], w["embed"]["unembed"],
            eps=arch.norm_eps, operand=None).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
