"""Mixture-of-Experts FFN: router, and three dispatches of the routed experts.

The layer may hold a share of the routed experts: the contiguous block
``[cfg.expert_first, cfg.expert_first + cfg.held_experts)``, as one rank of
an expert-parallel group does.  The router keeps its full ``num_experts``
outputs and routes over all of them; the layer computes the part of the
result that its own experts give, and an assignment to an expert it does
not hold contributes nothing here.  With the default share (all experts)
this is the whole layer.  Nothing stands in for the absent ranks or their
exchange.

Serving (prefill and decode) runs :func:`moe_serve`, the grouped dispatch:
exact and dropless, and no expert runs on a token not routed to it.  The
assignments that land on held experts are sorted by expert, each expert
projection is one ``jax.lax.ragged_dot`` over the held experts' groups,
and the weighted rows are scatter-added back to their tokens.

Training (:func:`moe_apply`) selects by ``ModelConfig.moe_impl``:

- ``dense``  — every held expert processes every token, outputs combined
  with the (sparse) router weights.  Simple, numerically exact,
  GSPMD-friendly (experts shard cleanly over the 'model' mesh axis), but
  compiled FLOPs are ``held / top_k`` times the useful work.  This is the
  *paper-faithful baseline* substrate: the roofline's MODEL_FLOPS/HLO_FLOPs
  ratio exposes the waste, and the §Perf hillclimb switches to gshard.

- ``gshard`` — capacity-based scatter dispatch (GShard/Switch style): tokens
  are routed into per-expert capacity buffers, experts run batched matmuls
  over their buffers only, results scatter back weighted by router probs.
  Compiled FLOPs ~ top_k x FFN (+ padding to capacity); tokens overflowing
  an expert's capacity are dropped (standard capacity-factor semantics).

Router: softmax over all experts in float32, greedy top-k, the selected
probabilities rescaled to sum 1 where ``cfg.norm_topk_prob`` (DeepSeek-V2
and deepseek-moe-16b leave them as they are), plus an auxiliary
load-balancing loss (Switch Transformer Eq. 4).  Shared experts run on
every token.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, ParamSpec
from .layers import mlp_apply, mlp_specs


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f, e, h = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    specs: Dict[str, ParamSpec] = {
        "router": ParamSpec((d, e), ("embed", "experts"), "scaled"),
        "experts": {
            "wi": ParamSpec((h, d, f), ("experts", "embed", "mlp"), "scaled"),
            "wg": ParamSpec((h, d, f), ("experts", "embed", "mlp"), "scaled"),
            "wo": ParamSpec((h, f, d), ("experts", "mlp", "embed"), "scaled"),
        },
    }
    if cfg.num_shared_experts > 0:
        # shared experts run on every token (DeepSeek-MoE fine-grained design)
        specs["shared"] = mlp_specs(cfg, d_ff=cfg.d_ff * cfg.num_shared_experts)
    return specs


def route(
    router_w: jax.Array, x: jax.Array, top_k: int, *, normalize: bool = True
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router: x (T, D) -> (probs (T, k), indices (T, k), aux_loss ()).

    Softmax over all experts in fp32; the top-k probabilities rescaled to
    sum 1 where ``normalize``.  Aux loss = E * sum_e f_e * p_e  (Switch
    Transformer load balancing).
    """
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs_full = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    probs, idx = jax.lax.top_k(probs_full, top_k)                # (T, k)
    if normalize:
        probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)

    e = router_w.shape[-1]
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)           # (T, k, E)
    frac_tokens = onehot.sum(axis=(0, 1)) / (x.shape[0] * top_k) # f_e
    mean_probs = probs_full.mean(axis=0)                         # p_e
    aux = e * jnp.sum(frac_tokens * mean_probs)
    return probs, idx, aux


def held_index(idx: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """(index among the held experts, whether the expert is held) of each
    routed expert index."""
    local = idx - cfg.expert_first
    return local, (local >= 0) & (local < cfg.held_experts)


def _dense_dispatch(
    params: Dict, x: jax.Array, probs: jax.Array, idx: jax.Array, cfg: ModelConfig
) -> jax.Array:
    """All held experts on all tokens; combine with sparse weights.
    x: (T, D)."""
    local, _ = held_index(idx, cfg)
    # (T, H) combine weights: zero for unselected or absent experts (an
    # index outside [0, H) one-hots to zeros)
    combine = jnp.einsum(
        "tk,tkh->th", probs,
        jax.nn.one_hot(local, cfg.held_experts, dtype=probs.dtype),
    ).astype(x.dtype)
    wi, wg, wo = params["experts"]["wi"], params["experts"]["wg"], params["experts"]["wo"]
    h = jnp.einsum("td,edf->tef", x, wi)
    g = jnp.einsum("td,edf->tef", x, wg)
    h = jax.nn.silu(g) * h
    y = jnp.einsum("tef,efd->ted", h, wo)
    return jnp.einsum("ted,te->td", y, combine)


def _gshard_dispatch(
    params: Dict, x: jax.Array, probs: jax.Array, idx: jax.Array, cfg: ModelConfig
) -> jax.Array:
    """Capacity-based scatter dispatch.  x: (T, D) -> (T, D).

    capacity C = ceil(T * top_k * capacity_factor / E).  Each (token, k)
    assignment to a held expert gets a slot in its expert's buffer if the
    expert is not full (position-in-expert via a cumulative count over the
    flattened assignment order); overflow assignments are dropped.
    """
    t, d = x.shape
    e, k, held = cfg.num_experts, cfg.moe_top_k, cfg.held_experts
    capacity = max(1, int((t * k * cfg.capacity_factor) / e))

    local, mine = held_index(idx, cfg)
    flat_expert = jnp.where(mine, local, 0).reshape(-1)          # (T*k,)
    flat_mine = mine.reshape(-1)
    flat_prob = probs.reshape(-1)                                # (T*k,)
    flat_token = jnp.repeat(jnp.arange(t), k)                    # (T*k,)

    onehot = jax.nn.one_hot(flat_expert, held, dtype=jnp.int32) * flat_mine[:, None]
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1)             # running count
    pos = jnp.take_along_axis(pos_in_expert, flat_expert[:, None], axis=1)[:, 0]
    keep = flat_mine & (pos < capacity)

    # scatter tokens into (H, C, D) buffers; dropped tokens write nowhere
    safe_pos = jnp.where(keep, pos, capacity - 1)
    buf = jnp.zeros((held, capacity, d), x.dtype)
    contrib = jnp.where(keep[:, None], x[flat_token], 0.0)
    buf = buf.at[flat_expert, safe_pos].add(contrib)

    wi, wg, wo = params["experts"]["wi"], params["experts"]["wg"], params["experts"]["wo"]
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)       # (H, C, D)

    # gather back, weight by router prob
    gathered = y[flat_expert, safe_pos]                          # (T*k, D)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    out = jnp.zeros((t, d), x.dtype).at[flat_token].add(
        gathered * flat_prob[:, None].astype(x.dtype)
    )
    return out


def _grouped_dispatch(
    params: Dict, x: jax.Array, probs: jax.Array, idx: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """Dropless dispatch over the held experts.  x: (T, D) -> ((T, D), the
    number of assignments that landed on held experts).

    The T * k assignments are sorted by held expert (the others last) and
    each projection is one ``ragged_dot`` over the held experts' groups, so
    an expert sees exactly the tokens routed to it.  The rows after the
    last group belong to absent experts: ``ragged_dot`` does not compute
    them, and they are masked out before the scatter-add, which sums in
    float32.
    """
    t, d = x.shape
    k = idx.shape[-1]
    held = cfg.held_experts
    local, mine = held_index(idx.reshape(-1), cfg)               # (T*k,)
    group = jnp.where(mine, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    token = order // k
    rows = x[token]                                              # (T*k, D)
    ew = params["experts"]
    h = jax.lax.ragged_dot(rows, ew["wi"], sizes)
    g = jax.lax.ragged_dot(rows, ew["wg"], sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(g) * h, ew["wo"], sizes)  # (T*k, D)
    w = probs.reshape(-1)[order]
    y = jnp.where(mine[order][:, None], y.astype(jnp.float32) * w[:, None], 0.0)
    out = jnp.zeros((t, d), jnp.float32).at[token].add(y)
    return out.astype(x.dtype), mine.sum(dtype=jnp.int32)


def _shared(params: Dict, xt: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("moe.shared"):
        return mlp_apply(params["shared"], xt, cfg.act)


def _route(params: Dict, xt: jax.Array, cfg: ModelConfig):
    with jax.named_scope("moe.router"):
        return route(params["router"], xt, cfg.moe_top_k,
                     normalize=cfg.norm_topk_prob)


def moe_apply(
    params: Dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN over x for training: (B, S, D) -> ((B, S, D), aux_loss)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    probs, idx, aux = _route(params, xt, cfg)
    with jax.named_scope("moe.experts"):
        if cfg.moe_impl == "gshard":
            y = _gshard_dispatch(params, xt, probs, idx, cfg)
        else:
            y = _dense_dispatch(params, xt, probs, idx, cfg)
    if cfg.num_shared_experts > 0:
        y = y + _shared(params, xt, cfg)
    return y.reshape(b, s, d), aux


def moe_serve(
    params: Dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN over x for serving, by the grouped dispatch: (B, S, D) ->
    ((B, S, D), the (token, expert) assignments that landed on held
    experts)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    probs, idx, _ = _route(params, xt, cfg)
    with jax.named_scope("moe.experts"):
        y, held = _grouped_dispatch(params, xt, probs, idx, cfg)
    if cfg.num_shared_experts > 0:
        y = y + _shared(params, xt, cfg)
    return y.reshape(b, s, d), held
