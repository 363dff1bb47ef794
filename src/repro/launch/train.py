"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --steps 20

Builds a ("data", "model") mesh over the process's devices (``1 x N`` unless
``--mesh`` says otherwise), plans GSPMD shardings for params / optimizer /
batches through the same ShardingPlanner the production dry-run uses, and
runs REAL sharded train steps on synthetic data with loss/step-time logging
and checkpointing.  A CPU rehearsal of a sharded mesh forces host devices
explicitly (``--host-devices 8 --mesh 2x4``); on a TPU pod the same entry
point runs with ``--mesh 16x16``.
"""

import argparse
import os
import sys


def _parse_args():
    ap = argparse.ArgumentParser(description="sharded training launcher")
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU rehearsal only: force N host devices")
    ap.add_argument("--mesh", default="",
                    help="mesh shape, e.g. 2x4 (data x model) or 2x4x4 "
                         "(pod x data x model); default 1 x devices")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--log-every", type=int, default=5)
    return ap.parse_args()


def main() -> None:
    args = _parse_args()
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}"
        )

    # jax may only be imported after the device-count flag is set
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.configs  # noqa: F401
    from ..configs.reduced import reduced_config
    from ..data.synthetic import DataConfig, SyntheticLM
    from ..models.registry import build_model, get_config
    from ..optim.adamw import AdamW, AdamWState
    from ..sharding.planner import ShardingPlanner
    from ..training.steps import make_train_step
    from ..checkpoint.io import save_checkpoint
    from .cache import use_compile_cache
    from .mesh import make_mesh

    use_compile_cache()
    dims = ([int(x) for x in args.mesh.split("x")] if args.mesh
            else [1, len(jax.devices())])
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    if len(dims) not in (2, 3):
        sys.exit("mesh must be 2- or 3-dimensional")
    if np.prod(dims) != len(jax.devices()):
        sys.exit(f"mesh {dims} needs {np.prod(dims)} devices, "
                 f"have {len(jax.devices())}")
    mesh = make_mesh(dims, names)

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    planner = ShardingPlanner(mesh, fsdp=True, context="train")
    param_sh = planner.param_shardings(model)

    opt = AdamW(learning_rate=args.lr)
    params = jax.device_put(model.init(jax.random.PRNGKey(0)), param_sh)
    opt_state = jax.device_put(
        opt.init(params),
        AdamWState(step=planner.replicated(), m=param_sh, v=param_sh),
    )

    lm = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=0,
    ))
    batches = lm.batches()
    sample = next(lm.batches())
    batch_sh = {k: planner.batch_spec(v.shape) for k, v in sample.items()}

    with jax.set_mesh(mesh):
        step_fn = jax.jit(
            make_train_step(model, opt),
            in_shardings=(param_sh, None, batch_sh),
            out_shardings=(planner.replicated(), param_sh, None),
        )
        print(f"{args.arch}{' (reduced)' if args.reduced else ''} on "
              f"{'x'.join(map(str, dims))} mesh ({len(jax.devices())} devices)")
        t_first = None
        for step in range(args.steps):
            batch = {k: jax.device_put(jnp.asarray(v), batch_sh[k])
                     for k, v in next(batches).items()}
            t0 = time.perf_counter()
            loss, params, opt_state = step_fn(params, opt_state, batch)
            loss = float(loss)
            dt = time.perf_counter() - t0
            if t_first is None:
                t_first = dt
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:4d}  loss {loss:.4f}  {dt * 1e3:.0f} ms")
    if args.checkpoint_dir:
        path = save_checkpoint(args.checkpoint_dir, args.steps,
                               jax.device_get(params))
        print(f"checkpoint: {path}")


if __name__ == "__main__":
    main()
