"""Weights drawn from the run's seed on the device.

The tree is the family's parameter layout (``Arch.leaf_specs``, spelled
out in ``families/<family>.py`` so that the benchmark and not the program
decides the values).  Each matrix gets standard deviation ``1 /
sqrt(its own fan-in)``, the scale of a trained model, under which bf16
serving stays close to float32 and a lower precision does not.  Norm
scales are ``1 + 0.1 N(0, 1)``.
Every leaf has a key of its own, folded from the seed's key by the leaf's
index in path order, so one call makes the same values for the program
(sharded, in its parameter dtype) and for the reference (float32).
"""

from __future__ import annotations

import gc
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1


def seed_key(seed: int):
    """A raw threefry key from any whole number, 64 bits and more
    included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def make(a, seed: int, *, dtype_of=None, shardings=None):
    """The whole tree in one jitted call on the device.

    ``dtype_of``: ``{path: dtype}`` of the leaves as served (default
    float32); ``shardings``: the tree's output shardings (default: the
    default device)."""
    specs = a.leaf_specs()
    paths = sorted(specs)
    dtype_of = dtype_of or {}

    def gen(key):
        flat = {}
        for i, path in enumerate(paths):
            shape, std = specs[path]
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            x = 1.0 + NORM_STD * z if std is None else z * std
            flat[path] = x.astype(dtype_of.get(path, jnp.float32))
        return a.nest(flat)

    return jax.jit(gen, out_shardings=shardings)(seed_key(seed))


def tree_paths(tree) -> Dict[Tuple[str, ...], object]:
    """``{path: leaf}`` of a parameter tree, with list indices as strings,
    in the form ``Arch.leaf_specs`` uses."""
    out = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                  for k in kp)] = leaf
    return out


def install(plane, a, seed: int) -> None:
    """Replace the serving plane's parameters with the seed's, of the same
    shapes, dtypes and shardings.  The program's own set is freed first,
    so that the two never live on the chip together."""
    now = plane.params
    if now is None:                     # freed earlier: the program's shapes
        now = plane.models["accurate"].abstract_params()
    held = {p: (tuple(x.shape), x.dtype) for p, x in tree_paths(now).items()}
    want = {p: shape for p, (shape, _) in a.leaf_specs().items()}
    if {p: s for p, (s, _) in held.items()} != want:
        raise ValueError(
            "the program's parameter tree differs from the benchmark's "
            f"layout of its family: {sorted(held)} vs {sorted(want)}")
    shardings = plane.param_sh
    dtype_of = {p: dt for p, (_, dt) in held.items()}
    plane.params = now = None
    gc.collect()
    plane.params = make(a, seed, dtype_of=dtype_of, shardings=shardings)
    jax.block_until_ready(plane.params)
