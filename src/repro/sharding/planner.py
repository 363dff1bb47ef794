"""Logical-axis sharding planner with divisibility fallback.

Every parameter / state tensor in the substrate carries a tuple of *logical
axis* names (see ``ParamSpec.axes``).  The planner maps logical axes to mesh
axes by priority rules, subject to:

  - a mesh axis is consumed at most once per tensor;
  - a dimension only takes a mesh axis whose size divides it (remaining
    size after earlier assignments) — otherwise the axis is skipped and the
    dim is (partially) replicated.  This is the fallback that handles e.g.
    hymba's 25 attention heads or granite's 49155 vocab on a 16-way
    tensor-parallel axis.

Rule sets:
  - ``train``  — tensor-parallel over "model" for heads/mlp/experts/vocab,
    FSDP over ("pod","data") on the "embed" dim of params, batch over
    ("pod","data").
  - ``serve``  — tensor-parallel only for params (weights stay resident,
    no FSDP gather per step wanted for latency); decode caches shard batch
    over ("pod","data") and the cache sequence over whatever is left
    (("data"|"model")), which is what makes the 500k-token cache fit.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

from ..models.common import ParamSpec as ModelParamSpec


# priority-ordered mesh-axis candidates per logical axis
def _rules(mesh_axes: Tuple[str, ...], *, fsdp: bool, context: str) -> Dict[str, Tuple[str, ...]]:
    data_axes = tuple(a for a in mesh_axes if a in ("pod", "data"))
    rules: Dict[str, Tuple[str, ...]] = {
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "mlp": ("model",),
        "experts": ("model",),
        "ssm_inner": ("model",),
        "head": (),
        "layers": (),
        "frontend": (),
        # train: FSDP.  serve: 2D weight sharding for big archs (the model
        # axis alone leaves e.g. llama3-405B at >100 GB/chip — weights must
        # also split over data; decode activations are tiny, so GSPMD pays a
        # small per-layer partial-sum/gather instead).  Enabled per-arch via
        # ``serve_weight_2d``.
        "embed": data_axes if (fsdp and context == "train") else (),
        # activations / states
        "batch": data_axes,
        "seq": (),
        "kv_seq": data_axes + ("model",),
        "enc_seq": (),
        "state": (),
    }
    return rules


@dataclass
class ShardingPlanner:
    mesh: Mesh
    fsdp: bool = True
    context: str = "train"        # train | serve
    fsdp_vocab: bool = False      # FSDP the embed dim of vocab-bearing params?
    serve_weight_2d: bool = False  # serve: also shard weight embed dims over data

    def __post_init__(self) -> None:
        self.mesh_axes = tuple(self.mesh.axis_names)
        self.axis_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.rules = _rules(self.mesh_axes, fsdp=self.fsdp, context=self.context)
        if self.context == "serve" and self.serve_weight_2d:
            data_axes = tuple(a for a in self.mesh_axes if a in ("pod", "data"))
            self.rules["embed"] = data_axes

    # -- core assignment ------------------------------------------------------

    def spec_for(self, shape: Sequence[int], axes: Sequence[Optional[str]]
                 ) -> PartitionSpec:
        if len(shape) != len(axes):
            raise ValueError(f"rank mismatch {shape} vs {axes}")
        used: set = set()
        dims = []
        # FSDP-sharding the embed dim of the (embed x vocab) projections makes
        # the unembed weight-grad contraction need FULL-batch dlogits per
        # chip: GSPMD all-gathers the fp32 logits over 'data' (67 GB/chip for
        # a 256k vocab at 1M tokens) instead of reduce-scattering the 0.2 GB
        # weight grad — measured §Perf pair B.  Keep those params
        # vocab-sharded only (a ~1 GB/chip optimizer-state cost).
        block_embed_fsdp = (not self.fsdp_vocab) and ("vocab" in axes)
        for size, logical in zip(shape, axes):
            assigned: list = []
            remaining = int(size)
            if logical == "embed" and block_embed_fsdp:
                logical = None
            if logical is not None:
                for mesh_ax in self.rules.get(logical, ()):
                    if mesh_ax not in self.axis_sizes or mesh_ax in used:
                        continue
                    ax_size = self.axis_sizes[mesh_ax]
                    if remaining % ax_size == 0 and remaining >= ax_size:
                        assigned.append(mesh_ax)
                        used.add(mesh_ax)
                        remaining //= ax_size
            if not assigned:
                dims.append(None)
            elif len(assigned) == 1:
                dims.append(assigned[0])
            else:
                dims.append(tuple(assigned))
        return PartitionSpec(*dims)

    def named(self, shape: Sequence[int], axes: Sequence[Optional[str]]
              ) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(shape, axes))

    # -- trees ------------------------------------------------------------------

    def tree_shardings(self, abstract_tree: Any, axes_tree: Any) -> Any:
        """NamedSharding tree for (ShapeDtypeStruct tree, logical-axes tree)."""
        return jax.tree.map(
            lambda leaf, ax: self.named(leaf.shape, ax),
            abstract_tree,
            axes_tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(a, (str, type(None))) for a in x
            ),
        )

    def param_shardings(self, model) -> Any:
        """Shardings for a Model's parameter tree."""
        return self.tree_shardings(model.abstract_params(), model.logical_axes())

    # -- batches / states ---------------------------------------------------------

    def batch_spec(self, shape: Sequence[int], kind: str = "tokens") -> NamedSharding:
        """Input batch arrays: dim 0 = global batch, rest replicated/seq."""
        axes: list = ["batch"] + ["seq"] * (len(shape) - 1)
        if kind == "embeds" and len(shape) == 3:
            axes = ["batch", "seq", None]
        return self.named(shape, axes)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())


def state_logical_axes(state_tree: Any, *, stack: int = 2) -> Any:
    """Logical axes for a decode-state pytree.

    States are stacked ``(repeat, count, ...)`` by the backbone (``stack=2``
    leading 'layers' dims).  Type-aware traversal: KVCache / SSMState /
    MLSTMState / SLSTMState leaves get their canonical axes; anything else
    falls back to (batch, replicated...).
    """
    from ..models.attention import KVCache
    from ..models.ssm import SSMState
    from ..models.xlstm import MLSTMState, SLSTMState

    lead = ("layers",) * stack

    def _rec(obj: Any) -> Any:
        if isinstance(obj, KVCache):
            scale_ax = lead + ("batch", "kv_seq", "kv_heads")
            return KVCache(
                k=lead + ("batch", "kv_seq", "kv_heads", "head"),
                v=lead + ("batch", "kv_seq", "kv_heads", "head"),
                index=lead if obj.index.ndim == stack else (None,) * obj.index.ndim,
                length=lead if obj.length.ndim == stack else (None,) * obj.length.ndim,
                k_scale=scale_ax if obj.k_scale is not None else None,
                v_scale=scale_ax if obj.v_scale is not None else None,
            )
        if isinstance(obj, SSMState):
            return SSMState(
                h=lead + ("batch", "ssm_inner", None),
                conv=lead + ("batch", None, "ssm_inner"),
            )
        if isinstance(obj, MLSTMState):
            return MLSTMState(
                c=lead + ("batch", "heads", "head", None),
                n=lead + ("batch", "heads", "head"),
                m=lead + ("batch", "heads"),
            )
        if isinstance(obj, SLSTMState):
            ax = lead + ("batch", "heads", "head")
            return SLSTMState(c=ax, n=ax, h=ax, m=ax)
        if isinstance(obj, dict):
            out = {}
            for k, v in obj.items():
                if k in ("enc_k", "enc_v"):
                    out[k] = lead + ("batch", "enc_seq", "kv_heads", "head")
                else:
                    out[k] = _rec(v)
            return out
        if isinstance(obj, (list, tuple)):
            t = type(obj)
            return t(_rec(v) for v in obj)
        if obj is None:
            return None
        # leaf array (e.g. "position" scalar)
        rank = getattr(obj, "ndim", 0)
        return (None,) * rank

    return _rec(state_tree)


def shard_hint(x, spec: Sequence[Optional[str]]):
    """GSPMD sharding hint from the ambient mesh (``jax.set_mesh``).

    ``spec`` entries are logical: "batch" (maps to the ("pod","data") axes),
    "model", or None.  Outside a mesh (single-device tests, the serving
    engine) this is a no-op, so model code can call it unconditionally.
    A mesh axis is applied only when it is an ``Auto`` axis and divides
    the dim.

    WHY: GSPMD's auto propagation may re-shard interior ops against the
    communication-optimal choice (measured on the unembed matmul: it split
    the contraction dim across 'data', turning a 0.2 GB weight gather into a
    67 GB fp32 logits all-reduce — §Perf pair B).  Pinning the activation
    layout at the producer removes the solver's freedom to do that.
    """
    mesh = jax.sharding.get_abstract_mesh()
    sizes = {name: size for name, size, kind in
             zip(mesh.axis_names, mesh.axis_sizes, mesh.axis_types)
             if kind == AxisType.Auto}
    if not sizes:
        return x
    dims = []
    for dim_size, s in zip(x.shape, spec):
        if s == "batch":
            axes = []
            rem = int(dim_size)
            for a in ("pod", "data"):
                if a in sizes and rem % sizes[a] == 0:
                    axes.append(a)
                    rem //= sizes[a]
            dims.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
        elif s == "model":
            ok = "model" in sizes and dim_size % sizes["model"] == 0
            dims.append("model" if ok else None)
        else:
            dims.append(None)
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*dims))
