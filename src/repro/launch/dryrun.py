"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes and extract roofline inputs.

MUST be run as its own process (``PYTHONPATH=src python -m repro.launch.dryrun``):
:func:`main` forces 512 placeholder host devices before jax initializes a
backend.  Importing the module changes nothing; tests call
:func:`lower_case` in a subprocess that forces its own device count
(tests/test_dryrun.py).
"""

import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import INPUT_SHAPES, LONG_CONTEXT_WINDOW, InputShape
from ..models.common import ModelConfig
from ..models.registry import arch_ids, build_model, get_config
from ..optim.adamw import AdamW, AdamWState
from ..sharding.planner import ShardingPlanner, state_logical_axes
from ..training.steps import (
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from .analytic import step_costs
from .mesh import make_production_mesh
from .roofline import RooflineReport, analyze, model_flops_for

HOST_DEVICE_FLAGS = "--xla_force_host_platform_device_count=512"

BIG_ARCHS = {"llama3-405b"}     # bf16 optimizer state to fit single-pod HBM


def effective_config(arch_id: str, shape_name: str) -> ModelConfig:
    """Apply per-shape adaptations (the long-context sub-quadratic variant)."""
    cfg = get_config(arch_id)
    if (
        shape_name == "long_500k"
        and cfg.family != "ssm"              # xlstm: attention-free already
        and cfg.sliding_window == 0
    ):
        cfg = dataclasses.replace(cfg, sliding_window=LONG_CONTEXT_WINDOW)
    return cfg


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this case.

    Returns {"batch": ...} for train/prefill and {"state":..., "token":...}
    for decode kinds.  No device allocation happens here.
    """
    b, s = shape.global_batch, shape.seq_len
    model = build_model(cfg)
    if shape.kind in ("train", "prefill"):
        batch: Dict[str, Any] = {}
        if cfg.family == "vlm":
            text = s - cfg.prefix_tokens
            batch["patch_embeds"] = _sds((b, cfg.prefix_tokens, cfg.prefix_dim), "bfloat16")
            batch["tokens"] = _sds((b, text), "int32")
            if shape.kind == "train":
                batch["labels"] = _sds((b, text), "int32")
        elif cfg.family == "audio":
            dec = max(1, s // cfg.decoder_len_ratio)
            batch["frames"] = _sds((b, s, cfg.prefix_dim), "bfloat16")
            batch["tokens"] = _sds((b, dec), "int32")
            if shape.kind == "train":
                batch["labels"] = _sds((b, dec), "int32")
        else:
            batch["tokens"] = _sds((b, s), "int32")
            if shape.kind == "train":
                batch["labels"] = _sds((b, s), "int32")
        return {"batch": batch}

    # decode: ONE new token against a standing cache/state of length s
    cache_len = model.cache_len_for(s)
    enc_len = (s // cfg.decoder_len_ratio) if cfg.family == "audio" else 0
    state = jax.eval_shape(
        lambda: model.init_decode_state(b, cache_len, enc_len=enc_len, position=0)
    )
    return {"state": state, "token": _sds((b,), "int32")}


def _opt_for(cfg: ModelConfig) -> AdamW:
    return AdamW(
        state_dtype="bfloat16" if cfg.arch_id in BIG_ARCHS else None
    )


def _opt_shardings(param_sh, planner: ShardingPlanner):
    return AdamWState(step=planner.replicated(), m=param_sh, v=param_sh)


def lower_case(
    arch_id: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    mesh=None,
    cfg: Optional[ModelConfig] = None,
    donate: bool = True,
    planner_kwargs: Optional[Dict[str, Any]] = None,
):
    """Build + lower one (arch, shape, mesh) case.  Returns (lowered, meta).

    ``mesh``/``cfg`` overrides let tests run reduced configs on tiny meshes
    through the exact same path.
    """
    shape = INPUT_SHAPES[shape_name]
    if cfg is None:
        cfg = effective_config(arch_id, shape_name)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    specs = input_specs(cfg, shape)
    params_abs = model.abstract_params()

    context = "train" if shape.kind == "train" else "serve"
    pk = dict(planner_kwargs or {})
    if context == "serve":
        # big archs cannot hold a model-axis weight shard per chip (llama3-405B
        # = >100 GB/chip); split weights over data too (2D weight sharding).
        pk.setdefault("serve_weight_2d", arch_id in BIG_ARCHS)
    planner = ShardingPlanner(mesh, fsdp=True, context=context, **pk)
    param_sh = planner.param_shardings(model)

    # Trace/lower under the mesh context so interior ``shard_hint``
    # constraints (PartitionSpec-based) bind to this mesh.
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = _opt_for(cfg)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            opt_sh = _opt_shardings(param_sh, planner)
            batch_sh = {
                k: planner.batch_spec(v.shape) for k, v in specs["batch"].items()
            }
            step = make_train_step(model, opt)
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(planner.replicated(), param_sh, opt_sh),
                donate_argnums=(0, 1) if donate else (),
            )
            lowered = jitted.lower(params_abs, opt_abs, specs["batch"])
        elif shape.kind == "prefill":
            batch_sh = {
                k: planner.batch_spec(v.shape) for k, v in specs["batch"].items()
            }
            cache_len = model.cache_len_for(shape.seq_len)
            step = make_prefill_step(model, cache_len=cache_len)
            jitted = jax.jit(step, in_shardings=(param_sh, batch_sh))
            lowered = jitted.lower(params_abs, specs["batch"])
        else:  # decode
            state_abs = specs["state"]
            state_sh = planner.tree_shardings(
                state_abs, state_logical_axes(state_abs)
            )
            token_sh = planner.batch_spec(specs["token"].shape)
            step = make_serve_step(model)
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, state_sh, token_sh),
                out_shardings=(token_sh, state_sh),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(params_abs, state_abs, specs["token"])

    meta = {
        "arch": arch_id,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(np.prod(mesh.devices.shape)),
        "cfg": cfg,
        "model": model,
    }
    return lowered, meta


def run_case(arch_id: str, shape_name: str, *, multi_pod: bool) -> Dict[str, Any]:
    t0 = time.time()
    lowered, meta = lower_case(arch_id, shape_name, multi_pod=multi_pod)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        mem_stats = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
            "alias_size_bytes": getattr(mem, "alias_size_in_bytes", 0),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_stats = {"error": str(e)}

    shape = INPUT_SHAPES[shape_name]
    cfg = meta["cfg"]
    dec_len = None
    if cfg.family == "audio":
        dec_len = max(1, shape.seq_len // cfg.decoder_len_ratio)
    mf = model_flops_for(cfg, shape.kind, shape.seq_len, shape.global_batch,
                         decoder_len=dec_len)
    costs = step_costs(
        cfg, shape.kind, shape.seq_len, shape.global_batch,
        opt_state_dtype_bytes=2 if cfg.arch_id in BIG_ARCHS else 4,
    )
    hlo = compiled.as_text()
    report = analyze(
        arch=arch_id,
        shape=shape_name,
        mesh_name=meta["mesh"],
        chips=meta["chips"],
        cost=dict(cost),
        hlo_text=hlo,
        model_flops=mf,
        analytic_flops=costs.flops,
        analytic_bytes=costs.hbm_bytes,
        memory_stats=mem_stats,
        compile_s=t_compile,
        note=costs.notes,
    )
    out = dataclasses.asdict(report)
    out["lower_s"] = t_lower
    out["kind"] = shape.kind
    return out


def main() -> None:
    os.environ["XLA_FLAGS"] = HOST_DEVICE_FLAGS
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun_results.jsonl")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args()

    arches = arch_ids() if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    n_ok = n_fail = 0
    with open(args.out, "a") as f:
        for arch in arches:
            for shape in shapes:
                for multi in meshes:
                    tag = f"{arch} x {shape} x {'2x16x16' if multi else '16x16'}"
                    t0 = time.time()
                    try:
                        rec = run_case(arch, shape, multi_pod=multi)
                        n_ok += 1
                        print(
                            f"[OK]   {tag}: compute={rec['compute_s']*1e3:.2f}ms "
                            f"memory={rec['memory_s']*1e3:.2f}ms "
                            f"collective={rec['collective_s']*1e3:.2f}ms "
                            f"bottleneck={rec['bottleneck']} "
                            f"useful={rec['useful_flops_ratio']:.2f} "
                            f"({time.time()-t0:.0f}s)",
                            flush=True,
                        )
                    except Exception as e:
                        n_fail += 1
                        rec = {
                            "arch": arch, "shape": shape,
                            "mesh": "2x16x16" if multi else "16x16",
                            "error": f"{type(e).__name__}: {e}",
                        }
                        print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
                        if args.fail_fast:
                            traceback.print_exc()
                            raise
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
