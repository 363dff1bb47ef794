"""Unified transformer-block layer: one spec/apply/decode interface over all
block types so the backbone can scan homogeneous segments.

Block types:
  - ``dense``   attn + MLP                          (llama/stablelm/minitron/...)
  - ``moe``     attn + MoE FFN                      (granite / deepseek)
  - ``hybrid``  parallel attn + Mamba SSM + MLP     (hymba)
  - ``mlstm``   mLSTM (no FFN; xLSTM-style block)
  - ``slstm``   sLSTM + MLP-less block
  - ``encoder`` bidirectional attn + MLP            (seamless encoder)
  - ``cross``   causal self-attn + cross-attn + MLP (seamless decoder)

Every block is pre-norm with residual connections.  Decode state is a
NamedTuple per type; stacked across layers by the backbone.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .common import ModelConfig, ParamSpec
from .layers import mlp_apply, mlp_specs, rmsnorm, rmsnorm_spec

BLOCK_TYPES = ("dense", "moe", "hybrid", "mlstm", "slstm", "encoder", "cross")


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------


def block_specs(cfg: ModelConfig, block_type: str, *, d_ff: Optional[int] = None) -> Dict:
    if block_type in ("dense", "encoder"):
        return {
            "attn_norm": rmsnorm_spec(cfg.d_model),
            "attn": attn.attention_specs(cfg),
            "mlp_norm": rmsnorm_spec(cfg.d_model),
            "mlp": mlp_specs(cfg, d_ff=d_ff),
        }
    if block_type == "moe":
        return {
            "attn_norm": rmsnorm_spec(cfg.d_model),
            "attn": attn.attention_specs(cfg),
            "mlp_norm": rmsnorm_spec(cfg.d_model),
            "moe": moe_mod.moe_specs(cfg),
        }
    if block_type == "hybrid":
        # Hymba: attention heads and SSM heads in parallel on the same input,
        # outputs averaged (arXiv:2411.13676), followed by an MLP.
        return {
            "mix_norm": rmsnorm_spec(cfg.d_model),
            "attn": attn.attention_specs(cfg),
            "ssm": ssm_mod.ssm_specs(cfg),
            "mlp_norm": rmsnorm_spec(cfg.d_model),
            "mlp": mlp_specs(cfg, d_ff=d_ff),
        }
    if block_type == "mlstm":
        return {
            "norm": rmsnorm_spec(cfg.d_model),
            "mlstm": xlstm_mod.mlstm_specs(cfg),
        }
    if block_type == "slstm":
        return {
            "norm": rmsnorm_spec(cfg.d_model),
            "slstm": xlstm_mod.slstm_specs(cfg),
        }
    if block_type == "cross":
        return {
            "attn_norm": rmsnorm_spec(cfg.d_model),
            "attn": attn.attention_specs(cfg),
            "cross_norm": rmsnorm_spec(cfg.d_model),
            "cross": attn.attention_specs(cfg, cross=True),
            "mlp_norm": rmsnorm_spec(cfg.d_model),
            "mlp": mlp_specs(cfg, d_ff=d_ff),
        }
    raise ValueError(f"unknown block type {block_type!r}")


# ---------------------------------------------------------------------------
# full-sequence apply (train / prefill)
# ---------------------------------------------------------------------------


def block_apply_seq(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    block_type: str,
    *,
    positions: Optional[jax.Array] = None,
    prefix_len: int = 0,
    enc_out: Optional[jax.Array] = None,
    ssm_mode: str = "serial",
    cache_len: int = 0,       # > 0: also build+return decode state (prefill)
) -> Tuple[jax.Array, jax.Array, Any]:
    """Apply one block to (B, S, D).  Returns (y, aux_loss, state|None).

    ``cache_len > 0`` marks the prefill path: attention blocks populate a
    KVCache of that size; recurrent blocks return their final states.
    """
    aux = jnp.zeros((), jnp.float32)
    state: Any = None
    if block_type in ("dense", "moe", "encoder", "cross"):
        h = rmsnorm(x, params["attn_norm"], cfg.norm_eps)
        res = attn.full_attention(
            params["attn"], h, cfg,
            positions=positions,
            causal=(block_type != "encoder"),
            window=cfg.sliding_window,
            prefix_len=prefix_len if block_type != "encoder" else 0,
            return_kv=cache_len > 0,
        )
        if cache_len > 0:
            h, (k, v) = res
            state = attn.cache_from_prefill(k, v, cache_len, cfg=cfg)
        else:
            h = res
        x = x + h
        if block_type == "cross":
            assert enc_out is not None, "cross block needs encoder output"
            h = rmsnorm(x, params["cross_norm"], cfg.norm_eps)
            h = attn.full_attention(params["cross"], h, cfg, kv_source=enc_out)
            x = x + h
            if cache_len > 0:
                enc_k, enc_v = attn.encode_cross_kv(params["cross"], enc_out)
                state = {"kv": state, "enc_k": enc_k, "enc_v": enc_v}
        h = rmsnorm(x, params["mlp_norm"], cfg.norm_eps)
        if block_type == "moe" and cache_len > 0:
            h, _ = moe_mod.moe_serve(params["moe"], h, cfg)
            state = moe_state(state)
        elif block_type == "moe":
            h, aux = moe_mod.moe_apply(params["moe"], h, cfg)
        else:
            h = mlp_apply(params["mlp"], h, cfg.act)
        return x + h, aux, state

    if block_type == "hybrid":
        h = rmsnorm(x, params["mix_norm"], cfg.norm_eps)
        res = attn.full_attention(
            params["attn"], h, cfg,
            positions=positions, causal=True, window=cfg.sliding_window,
            return_kv=cache_len > 0,
        )
        if cache_len > 0:
            a, (k, v) = res
            sres = ssm_mod.ssm_apply_seq(params["ssm"], h, cfg, mode=ssm_mode,
                                         return_state=True)
            s, ssm_state = sres
            state = {"kv": attn.cache_from_prefill(k, v, cache_len, cfg=cfg), "ssm": ssm_state}
        else:
            a = res
            s = ssm_mod.ssm_apply_seq(params["ssm"], h, cfg, mode=ssm_mode)
        x = x + 0.5 * (a + s)
        h = rmsnorm(x, params["mlp_norm"], cfg.norm_eps)
        return x + mlp_apply(params["mlp"], h, cfg.act), aux, state

    if block_type == "mlstm":
        h = rmsnorm(x, params["norm"], cfg.norm_eps)
        if cache_len > 0:
            y, state = xlstm_mod.mlstm_apply_seq(params["mlstm"], h, cfg,
                                                 return_state=True)
        else:
            y = xlstm_mod.mlstm_apply_seq(params["mlstm"], h, cfg)
        return x + y, aux, state

    if block_type == "slstm":
        h = rmsnorm(x, params["norm"], cfg.norm_eps)
        if cache_len > 0:
            y, state = xlstm_mod.slstm_apply_seq(params["slstm"], h, cfg,
                                                 return_state=True)
        else:
            y = xlstm_mod.slstm_apply_seq(params["slstm"], h, cfg)
        return x + y, aux, state

    raise ValueError(f"unknown block type {block_type!r}")


# ---------------------------------------------------------------------------
# decode state + single-token apply
# ---------------------------------------------------------------------------


def moe_state(kv: Any) -> Dict[str, Any]:
    """A MoE block's decode state: its KV cache, and ``held``, the
    (token, expert) assignments its decode steps have routed to the experts
    this chip holds since the prefill (an int32 counter on the device)."""
    return {"kv": kv, "held": jnp.zeros((), jnp.int32)}


def block_init_state(
    cfg: ModelConfig, block_type: str, batch: int, cache_len: int,
    dtype: jnp.dtype, *, enc_len: int = 0,
) -> Any:
    if block_type == "dense":
        return attn.init_cache(cfg, batch, cache_len, dtype)
    if block_type == "moe":
        return moe_state(attn.init_cache(cfg, batch, cache_len, dtype))
    if block_type == "hybrid":
        return {
            "kv": attn.init_cache(cfg, batch, cache_len, dtype),
            "ssm": ssm_mod.init_ssm_state(cfg, batch, dtype),
        }
    if block_type == "mlstm":
        return xlstm_mod.init_mlstm_state(cfg, batch)
    if block_type == "slstm":
        return xlstm_mod.init_slstm_state(cfg, batch)
    if block_type == "cross":
        return {
            "kv": attn.init_cache(cfg, batch, cache_len, dtype),
            # encoder K/V computed once at prefill
            "enc_k": jnp.zeros((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype),
            "enc_v": jnp.zeros((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype),
        }
    raise ValueError(f"no decode state for block type {block_type!r}")


def block_apply_decode(
    params: Dict,
    x: jax.Array,
    state: Any,
    cfg: ModelConfig,
    block_type: str,
    *,
    position: jax.Array,
    ssm_mode: str = "serial",
) -> Tuple[jax.Array, Any]:
    """One-token decode through one block.  x: (B, 1, D)."""
    if block_type == "dense":
        h = rmsnorm(x, params["attn_norm"], cfg.norm_eps)
        h, new_state = attn.decode_attention(params["attn"], h, state, cfg,
                                             position=position)
        x = x + h
        h = rmsnorm(x, params["mlp_norm"], cfg.norm_eps)
        return x + mlp_apply(params["mlp"], h, cfg.act), new_state

    if block_type == "moe":
        h = rmsnorm(x, params["attn_norm"], cfg.norm_eps)
        h, new_kv = attn.decode_attention(params["attn"], h, state["kv"], cfg,
                                          position=position)
        x = x + h
        h = rmsnorm(x, params["mlp_norm"], cfg.norm_eps)
        h, held = moe_mod.moe_serve(params["moe"], h, cfg)
        return x + h, {"kv": new_kv, "held": state["held"] + held}

    if block_type == "hybrid":
        h = rmsnorm(x, params["mix_norm"], cfg.norm_eps)
        a, new_kv = attn.decode_attention(params["attn"], h, state["kv"], cfg,
                                          position=position)
        s, new_ssm = ssm_mod.ssm_apply_decode(params["ssm"], h, state["ssm"], cfg)
        x = x + 0.5 * (a + s)
        h = rmsnorm(x, params["mlp_norm"], cfg.norm_eps)
        x = x + mlp_apply(params["mlp"], h, cfg.act)
        return x, {"kv": new_kv, "ssm": new_ssm}

    if block_type == "mlstm":
        h = rmsnorm(x, params["norm"], cfg.norm_eps)
        y, new_state = xlstm_mod.mlstm_apply_decode(params["mlstm"], h, state, cfg)
        return x + y, new_state

    if block_type == "slstm":
        h = rmsnorm(x, params["norm"], cfg.norm_eps)
        y, new_state = xlstm_mod.slstm_apply_decode(params["slstm"], h, state, cfg)
        return x + y, new_state

    if block_type == "cross":
        h = rmsnorm(x, params["attn_norm"], cfg.norm_eps)
        h, new_kv = attn.decode_attention(params["attn"], h, state["kv"], cfg,
                                          position=position)
        x = x + h
        h = rmsnorm(x, params["cross_norm"], cfg.norm_eps)
        h = attn.decode_cross_attention(params["cross"], h, state["enc_k"], state["enc_v"])
        x = x + h
        h = rmsnorm(x, params["mlp_norm"], cfg.norm_eps)
        x = x + mlp_apply(params["mlp"], h, cfg.act)
        return x, {"kv": new_kv, "enc_k": state["enc_k"], "enc_v": state["enc_v"]}

    raise ValueError(f"unknown block type {block_type!r}")
