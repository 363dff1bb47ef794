"""Analytic FLOP / HBM-byte model for every architecture x input shape.

WHY THIS EXISTS: XLA's ``compiled.cost_analysis()`` counts a while-loop body
ONCE, not trip-count times (verified empirically — a 10-iteration scanned
matmul reports 1/10th the FLOPs of its unrolled twin).  Every backbone here
scans over layers, so raw cost_analysis under-reports by ~num_layers.  The
roofline therefore uses:

  - compute term: THIS analytic model (exact math of our own modules);
  - memory term: THIS analytic traffic model (params + activations + states);
  - collective term: HLO parse with while trip-count correction
    (:mod:`repro.launch.roofline`);
  - raw cost_analysis values are reported alongside for transparency.

All counts are GLOBAL (whole step, all chips); callers divide by chips.
A matmul of (m, k) x (k, n) counts 2*m*k*n FLOPs.  Backward = 2x forward
(two matmuls per forward matmul); remat="full" adds one extra forward.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

from ..models.common import ModelConfig
from ..models.ssm import dt_rank


@dataclass(frozen=True)
class StepCosts:
    flops: float          # global FLOPs per step
    param_bytes: float    # bytes of parameters (param_dtype)
    act_bytes: float      # activation traffic (see memory model below)
    state_bytes: float    # KV-cache / recurrent-state traffic per step
    notes: str = ""

    @property
    def hbm_bytes(self) -> float:
        return self.param_bytes + self.act_bytes + self.state_bytes


def _attn_flops(cfg: ModelConfig, s: int, kv_len: Optional[int] = None,
                *, cross_kv: Optional[int] = None) -> float:
    """Per-sequence attention FLOPs (q from s positions)."""
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kvl = kv_len if kv_len is not None else s
    if cfg.sliding_window > 0:
        kvl = min(kvl, cfg.sliding_window)
    src = cross_kv if cross_kv is not None else s
    f = 0.0
    f += 2 * s * d * h * hd            # q proj
    f += 2 * src * d * kv * hd * 2     # k, v proj (on kv source)
    if cross_kv is not None:
        kvl = cross_kv
    # scores + values: causal halves the average kv length for self-attn
    eff = kvl if cross_kv is not None else max(1, kvl // 2) if kvl == s else kvl
    f += 2 * s * h * hd * eff * 2      # qk^T and pv
    f += 2 * s * h * hd * d            # out proj
    return f


def _mlp_flops(cfg: ModelConfig, s: int, d_ff: Optional[int] = None) -> float:
    f_dim = d_ff if d_ff is not None else cfg.d_ff
    n_mat = 3 if cfg.act in ("swiglu", "geglu") else 2
    return 2 * s * cfg.d_model * f_dim * n_mat


def _moe_flops(cfg: ModelConfig, s: int) -> float:
    d, f_dim, e, k = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.moe_top_k
    held = cfg.held_experts
    router = 2 * s * d * e
    if cfg.moe_impl == "gshard":
        # capacity buffers of the held experts: H * C tokens, C = s*k*cf/E
        cap_tokens = s * k * cfg.capacity_factor * held / e
        expert = 2 * cap_tokens * d * f_dim * 3
    else:
        # dense dispatch: every held expert touches every token
        expert = 2 * s * held * d * f_dim * 3
    shared = 0.0
    if cfg.num_shared_experts:
        shared = 2 * s * d * (f_dim * cfg.num_shared_experts) * 3
    return router + expert + shared


def _ssm_flops(cfg: ModelConfig, s: int) -> float:
    d, di, n = cfg.d_model, cfg.ssm_inner, cfg.ssm_state
    r = dt_rank(cfg)
    f = 0.0
    f += 2 * s * d * 2 * di            # in_proj
    f += 2 * s * di * cfg.ssm_conv     # conv (depthwise)
    f += 2 * s * di * (r + 2 * n)      # x_proj
    f += 2 * s * r * di                # dt_proj
    f += s * di * n * 6                # recurrence: decay*h + drive, y=C.h
    f += 2 * s * di * d                # out_proj
    return f


def _mlstm_flops(cfg: ModelConfig, s: int) -> float:
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    f = 2 * s * d * h * hd * 4         # q,k,v,o_gate projections
    f += 2 * s * d * h * 2             # i, f gates
    f += s * h * hd * hd * 4           # C update (outer product + decay) + C q
    f += 2 * s * h * hd * d            # out proj
    return f


def _slstm_flops(cfg: ModelConfig, s: int) -> float:
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    f = 2 * s * d * h * hd * 4         # w gates
    f += 2 * s * h * hd * hd * 4       # recurrent r gates
    f += s * h * hd * 8                # elementwise cell math
    f += 2 * s * h * hd * d            # out proj
    return f


def _layer_flops(cfg: ModelConfig, block_type: str, s: int,
                 kv_len: Optional[int] = None, enc_len: int = 0) -> float:
    if block_type == "dense":
        return _attn_flops(cfg, s, kv_len) + _mlp_flops(cfg, s)
    if block_type == "encoder":
        return _attn_flops(cfg, s, s) + _mlp_flops(cfg, s)
    if block_type == "cross":
        return (
            _attn_flops(cfg, s, kv_len)
            + _attn_flops(cfg, s, cross_kv=enc_len)
            + _mlp_flops(cfg, s)
        )
    if block_type == "moe":
        return _attn_flops(cfg, s, kv_len) + _moe_flops(cfg, s)
    if block_type == "hybrid":
        return _attn_flops(cfg, s, kv_len) + _ssm_flops(cfg, s) + _mlp_flops(cfg, s)
    if block_type == "mlstm":
        return _mlstm_flops(cfg, s)
    if block_type == "slstm":
        return _slstm_flops(cfg, s)
    raise ValueError(block_type)


def _decoder_flops(cfg: ModelConfig, s: int, kv_len: Optional[int] = None,
                   enc_len: int = 0) -> float:
    from ..models.transformer import derive_layout

    repeat, pattern = derive_layout(cfg)
    f = 0.0
    for block_type, count in pattern:
        f += repeat * count * _layer_flops(cfg, block_type, s, kv_len, enc_len)
    if cfg.family == "moe" and cfg.first_dense_layers:
        f += cfg.first_dense_layers * (
            _attn_flops(cfg, s, kv_len) + _mlp_flops(cfg, s, d_ff=cfg.dense_ff or cfg.d_ff)
        )
    return f


def _param_bytes(cfg: ModelConfig) -> float:
    from ..models.registry import build_model
    import numpy as np
    import jax

    model = build_model(cfg)
    bytes_per = 4 if cfg.param_dtype == "float32" else 2
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(model.abstract_params()))
    return float(n) * bytes_per


_PB_CACHE: Dict[str, float] = {}


def param_bytes_cached(cfg: ModelConfig) -> float:
    key = f"{cfg.arch_id}/{cfg.num_layers}/{cfg.d_model}/{cfg.sliding_window}"
    if key not in _PB_CACHE:
        _PB_CACHE[key] = _param_bytes(cfg)
    return _PB_CACHE[key]


def step_costs(cfg: ModelConfig, kind: str, seq_len: int, global_batch: int,
               *, opt_state_dtype_bytes: int = 4) -> StepCosts:
    """Global analytic costs for one step of (cfg, shape kind)."""
    b, s = global_batch, seq_len
    act_dtype = 2 if cfg.dtype == "bfloat16" else 4
    d = cfg.d_model
    l_total = cfg.num_layers + cfg.encoder_layers
    pbytes = param_bytes_cached(cfg)

    if kind in ("train", "prefill"):
        dec_s = s
        enc_len = 0
        if cfg.family == "audio":
            dec_s = max(1, s // cfg.decoder_len_ratio)
            enc_len = s
        fwd = _decoder_flops(cfg, dec_s, enc_len=enc_len) * b
        if cfg.family == "audio":
            fwd += cfg.encoder_layers * (
                _attn_flops(cfg, s, s) + _mlp_flops(cfg, s)
            ) * b
        # unembed (+ embed lookup is gather, ~free)
        out_positions = dec_s
        fwd += 2 * b * out_positions * d * cfg.vocab_size

        if kind == "prefill":
            flops = fwd
            # params read once; activations written once (and the KV cache)
            act = b * (s + dec_s) * d * act_dtype * l_total * 2
            cache = b * dec_s * cfg.num_kv_heads * cfg.head_dim * 2 * act_dtype * cfg.num_layers
            return StepCosts(flops=flops, param_bytes=pbytes,
                             act_bytes=act, state_bytes=cache)

        mult = 3.0 if cfg.remat == "none" else 4.0   # fwd+bwd (+re-fwd)
        flops = fwd * mult
        # params: fwd read + bwd read + grads write + opt read(p,m,v) +
        # write(p,m,v) — m/v in opt dtype
        opt_traffic = pbytes * 2 + 3 * pbytes  # fwd/bwd reads + p rw + grads
        opt_traffic += 4 * (pbytes / 4 * opt_state_dtype_bytes)  # m,v r+w
        # activations: with remat, only sqrt-ish checkpoints are stored; we
        # charge one write + one read of the per-layer residual stream
        act = b * (s + (dec_s if cfg.family == "audio" else 0)) * d * act_dtype
        act *= l_total * (2 if cfg.remat == "none" else 1) * 2
        return StepCosts(flops=flops, param_bytes=opt_traffic,
                         act_bytes=act, state_bytes=0.0,
                         notes=f"remat={cfg.remat}")

    # decode: one token per sequence
    kv_len = seq_len if cfg.sliding_window == 0 else min(cfg.sliding_window, seq_len)
    enc_len = (seq_len // cfg.decoder_len_ratio) if cfg.family == "audio" else 0
    flops = _decoder_flops(cfg, 1, kv_len=kv_len, enc_len=enc_len) * b
    flops += 2 * b * d * cfg.vocab_size
    # params read once per step; full KV cache / state read once
    if cfg.family == "ssm":
        # mLSTM matrix memory per layer
        state = cfg.num_layers * b * cfg.num_heads * cfg.head_dim ** 2 * 4
    else:
        kv_bytes = 1 if cfg.kv_cache_dtype == "int8" else act_dtype
        state = cfg.num_layers * b * kv_len * cfg.num_kv_heads * cfg.head_dim * 2 * kv_bytes
        if cfg.kv_cache_dtype == "int8":
            # per-(token, kv-head) fp32 absmax scales for k and v
            state += cfg.num_layers * b * kv_len * cfg.num_kv_heads * 2 * 4
        if cfg.family == "hybrid":
            state += cfg.num_layers * b * cfg.ssm_inner * cfg.ssm_state * 4
    act = b * d * act_dtype * l_total * 4
    return StepCosts(flops=flops, param_bytes=pbytes, act_bytes=act,
                     state_bytes=float(state))


# ---------------------------------------------------------------------------
# Serving-configuration cost model (production-plane Compass integration)
# ---------------------------------------------------------------------------

def serving_config_costs(cfg: ModelConfig, serving: Dict,
                         *, seq_len: int = 32768, chips: int = 256
                         ) -> "tuple[float, float]":
    """(relative_accuracy, per-request service time) for a serving config.

    The production plane exposes each architecture's accuracy/latency knobs —
    quantization dtype, attention window, MoE top-k, batch cap — as a Compass
    configuration space (DESIGN.md §2b).  Accuracy is *relative* to the
    full-quality configuration (1.0 = unchanged); latency is the analytic
    decode step time on a v5e pod slice divided across the batch.

    Quality model (documented deltas, order-of-magnitude from the quantization
    / windowed-attention / MoE-sparsity literature; exact values are knobs):
      int8 weights:      -1.5% relative accuracy
      window 4096/32k:   -1%   (distant-context loss)
      window 1024/32k:   -3%
      top-k k' < k:      -(1 - k'/k) * 6%
    """
    import dataclasses as _dc

    from .mesh import HBM_BW, PEAK_FLOPS_BF16

    quant = serving.get("quant", "bf16")
    window = serving.get("window", 0)
    top_k = serving.get("moe_top_k", cfg.moe_top_k)
    batch = serving.get("batch_cap", 16)

    acc = 1.0
    if quant == "int8":
        acc -= 0.015
    if window and seq_len > window:
        acc -= 0.03 if window <= 1024 else 0.01
    if cfg.num_experts and top_k < cfg.moe_top_k:
        acc -= (1.0 - top_k / cfg.moe_top_k) * 0.06

    eff = cfg
    over = {}
    if window:
        over["sliding_window"] = int(window)
    if cfg.num_experts and top_k != cfg.moe_top_k:
        over["moe_top_k"] = int(top_k)
    if over:
        eff = _dc.replace(cfg, **over)

    costs = step_costs(eff, "decode", seq_len, batch)
    bytes_total = costs.hbm_bytes
    if quant == "int8":
        bytes_total -= costs.param_bytes / 2  # int8 halves weight traffic
    compute_s = (costs.flops / chips) / PEAK_FLOPS_BF16
    memory_s = (bytes_total / chips) / HBM_BW
    step_s = max(compute_s, memory_s)
    # service time per REQUEST: decode step amortized over the batch, with a
    # nominal 64-token response
    service_s = step_s / batch * 64
    return acc, service_s
