"""Model substrate: configuration, parameter specs, initialization.

Every architecture in the pool is described by a :class:`ModelConfig` and
built by :mod:`repro.models.registry` into a :class:`Model` exposing

  - ``init(key)``            -> parameter pytree (real arrays)
  - ``abstract_params()``    -> ShapeDtypeStruct pytree (dry-run, no alloc)
  - ``logical_axes()``       -> pytree of logical-axis tuples (sharding)
  - ``train_loss(params, batch)``, ``prefill(params, tokens)``,
    ``decode_step(params, state, token, pos)``

Parameters are plain nested dicts of jnp arrays; layers are stacked on a
leading axis and traversed with ``jax.lax.scan`` so that the HLO contains one
layer body regardless of depth (critical for 126-layer dry-run compile times).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """Complete architectural description (one per assigned architecture)."""

    arch_id: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // num_heads

    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    moe_top_k: int = 0
    first_dense_layers: int = 0  # leading dense-FFN layers (DeepSeek-MoE)
    dense_ff: int = 0            # their hidden size
    moe_impl: str = "dense"      # dense | gshard   (training dispatch; serving
                                 # always runs the dropless grouped dispatch)
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True  # rescale the top-k probabilities to sum 1
    expert_first: int = 0        # this chip's share of the routed experts:
    experts_held: int = 0        # [first, first + held); 0 = all of them

    # --- SSM / hybrid (Mamba-style selective scan) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2

    # --- xLSTM ---
    slstm_every: int = 0         # one sLSTM block every N layers (0 = all mLSTM)

    # --- encoder-decoder ---
    encoder_layers: int = 0
    cross_attention: bool = False
    decoder_len_ratio: int = 1   # train/prefill decoder length = seq // ratio

    # --- stubbed modality frontend (VLM patch / audio frame embeddings) ---
    prefix_tokens: int = 0
    prefix_dim: int = 0          # frontend output dim (projector -> d_model)
    prefix_lm: bool = False      # bidirectional attention over the prefix

    # --- attention ---
    sliding_window: int = 0      # 0 = full; >0 = sliding-window causal
    rope_theta: float = 1.0e4

    # --- numerics / misc ---
    sharded_ce: bool = True      # GSPMD-friendly cross-entropy (see layers.py)
    act_hints: bool = True       # pin activation layouts via shard_hint
    kv_cache_dtype: str = ""     # "" = activation dtype; "int8" = quantized KV
    norm_eps: float = 1.0e-5
    act: str = "swiglu"          # swiglu | gelu
    tied_embeddings: bool = False
    dtype: str = "bfloat16"      # activation dtype
    param_dtype: str = "float32"
    remat: str = "none"          # none | full | dots_saveable
    scan_layers: bool = True
    logit_softcap: float = 0.0

    def __post_init__(self) -> None:
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.num_heads % max(1, self.num_kv_heads) != 0:
            raise ValueError(
                f"{self.arch_id}: num_heads {self.num_heads} not divisible by "
                f"kv heads {self.num_kv_heads}"
            )
        if self.family in ("moe",) and (self.num_experts <= 0 or self.moe_top_k <= 0):
            raise ValueError(f"{self.arch_id}: moe family needs experts/top_k")
        if self.num_experts and not (
                0 <= self.expert_first
                and self.expert_first + self.held_experts <= self.num_experts):
            raise ValueError(
                f"{self.arch_id}: held experts [{self.expert_first}, "
                f"{self.expert_first + self.held_experts}) outside the "
                f"{self.num_experts} routed")

    # -- derived ------------------------------------------------------------

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.num_experts

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def activation_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @property
    def parameter_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.param_dtype)

    def param_count(self) -> int:
        """Exact parameter count from the spec tree."""
        from .registry import build_model  # late import to avoid cycle

        model = build_model(self)
        return sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(model.abstract_params())
        )

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: routed top-k + shared only)."""
        total = self.param_count()
        if self.num_experts <= 0:
            return total
        from .registry import build_model

        model = build_model(self)
        specs = model.abstract_params()
        inactive = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(specs)[0]:
            keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
            if any("experts" in str(k) for k in keys):
                n = int(np.prod(leaf.shape))
                inactive += n - n * self.moe_top_k // self.num_experts
        return total - inactive

    def reduced(self, **overrides: Any) -> "ModelConfig":
        """A smoke-test variant of the same family (2 layers, narrow dims,
        few experts) that runs a real forward/train step on CPU."""
        small: Dict[str, Any] = dict(
            num_layers=2,
            d_model=min(self.d_model, 128),
            num_heads=min(self.num_heads, 4),
            num_kv_heads=min(self.num_kv_heads, 2),
            head_dim=32,
            d_ff=min(self.d_ff, 256) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            dtype="float32",
            param_dtype="float32",
            remat="none",
        )
        if self.num_heads % min(self.num_heads, 4) != 0:
            small["num_heads"] = 1
        if small["num_heads"] % max(1, small["num_kv_heads"]) != 0:
            small["num_kv_heads"] = 1
        if self.num_experts:
            small.update(
                num_experts=min(self.num_experts, 4),
                moe_top_k=min(self.moe_top_k, 2),
                num_shared_experts=min(self.num_shared_experts, 1),
                first_dense_layers=min(self.first_dense_layers, 1),
                dense_ff=min(self.dense_ff, 256) if self.dense_ff else 0,
                expert_first=0,
                experts_held=0,
            )
        if self.encoder_layers:
            small["encoder_layers"] = 2
        if self.prefix_tokens:
            small.update(prefix_tokens=8, prefix_dim=min(self.prefix_dim, 64))
        if self.sliding_window:
            small["sliding_window"] = min(self.sliding_window, 64)
        if self.slstm_every:
            small["slstm_every"] = 2
        small.update(overrides)
        return dataclasses.replace(self, **small)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    """Shape + logical sharding axes + initializer scale for one parameter."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]     # logical axis name per dim (None = replicated)
    init: str = "normal"                # normal | zeros | ones | scaled
    scale: float = 1.0

    def __post_init__(self) -> None:
        if len(self.shape) != len(self.axes):
            raise ValueError(f"spec rank mismatch: {self.shape} vs {self.axes}")


def init_param(key: jax.Array, spec: ParamSpec, dtype: jnp.dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    fan_in = spec.shape[0] if len(spec.shape) > 1 else max(1, spec.shape[0])
    if spec.init == "scaled":
        std = spec.scale / math.sqrt(fan_in)
    else:
        std = spec.scale * 0.02
    return (jax.random.normal(key, spec.shape) * std).astype(dtype)


def init_params(specs: Any, key: jax.Array, dtype: jnp.dtype) -> Any:
    """Initialize a pytree of ParamSpec into real arrays (deterministic
    per-leaf fold-in of the path hash)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))
    arrays = [init_param(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, arrays)


def abstract_params(specs: Any, dtype: jnp.dtype) -> Any:
    """ShapeDtypeStruct pytree — dry-run stand-in, no allocation."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def logical_axes(specs: Any) -> Any:
    """Pytree of logical-axis tuples, same structure as the param tree."""
    return jax.tree.map(
        lambda s: s.axes, specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def stacked(spec: ParamSpec, layers: int) -> ParamSpec:
    """Stack a per-layer spec on a leading 'layers' axis (scan-compatible)."""
    return ParamSpec(
        shape=(layers,) + spec.shape,
        axes=("layers",) + spec.axes,
        init=spec.init,
        scale=spec.scale,
    )
