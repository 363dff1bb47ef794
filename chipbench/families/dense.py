"""The dense SwiGLU decoder: sizes, parameter layout, plain reference and
step costs.

Parameter layout, the serving program's, spelled out here so that the
benchmark and not the program decides the values::

    embed:   embedding (V, d), final_norm (d,), unembed (d, V)
    decoder: [ {attn_norm, attn: {wq, wk, wv, wo}, mlp_norm,
                mlp: {wi, wg, wo}} ]      each stacked (1, layers, ...)

Each matrix gets standard deviation ``1 / sqrt(its own fan-in)``, the
scale of a trained model.  The embedding is looked up by a one-hot input
(fan-in 1), so its std is 1; ``None`` marks a norm scale.

The reference (``Arch.logits``): token embedding, then per layer RMSNorm,
q/k/v projections, rotary embedding (half-split, over the whole head),
grouped-query softmax attention with a causal mask (and a sliding window
on the fast rung), output projection and residual, RMSNorm, SwiGLU and
residual; a final RMSNorm and the output head, all from
``reference.py``'s float32 pieces.

The costs follow the decode and prefill branches of the program's
analytic cost model, with three differences, each for the work a step
needs rather than what an implementation happens to do: weights are
counted at the dtype the rung computes with (bf16), not at the dtype the
master copy is kept in; causal attention in a prefill sums the keys each
query attends exactly (``sum(min(q + 1, window))``) instead of half the
length; a prefill yields the logits of its last position only, as the
serving path does.  A matmul of (m, k) x (k, n) counts ``2 m k n``
operations; everything is per call of one program on one chip.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference as ref
from ..costs import DTYPE_BYTES, Cost, attended


@dataclass(frozen=True)
class Arch:
    """The sizes of one dense decoder, as its configuration file states."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ffn=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]))

    # -- the program ------------------------------------------------------

    @staticmethod
    def program_config(config: dict):
        """The program's model configuration with the sizes of the cell's
        configuration file; every size the file states has to hold."""
        from repro.models.registry import get_config

        base = get_config(config["program_arch"])
        sizes = dict(num_layers=config["num_hidden_layers"],
                     d_model=config["hidden_size"],
                     num_heads=config["num_attention_heads"],
                     num_kv_heads=config["num_key_value_heads"],
                     head_dim=config["head_dim"],
                     d_ff=config["intermediate_size"],
                     vocab_size=config["vocab_size"],
                     rope_theta=float(config["rope_theta"]),
                     norm_eps=float(config["rms_norm_eps"]),
                     dtype=config["serving"]["dtype"],
                     param_dtype=config["serving"]["param_dtype"],
                     tied_embeddings=bool(config["tie_word_embeddings"]))
        cfg = dataclasses.replace(base, **sizes)
        if cfg.family != "dense" or cfg.act != "swiglu":
            raise ValueError(f"{config['name']}: the program's model is "
                             f"{cfg.family}/{cfg.act}, the file states "
                             f"dense/swiglu")
        return cfg

    # -- parameters -------------------------------------------------------

    def leaf_specs(self) -> dict:
        """``{path: (shape, std)}``; std ``None`` marks a norm scale."""
        L, d, h, kv, hd, f, v = (self.layers, self.d, self.heads,
                                 self.kv_heads, self.head_dim, self.ffn,
                                 self.vocab)
        seg = ("decoder", "0")
        return {
            ("embed", "embedding"): ((v, d), 1.0),
            ("embed", "final_norm"): ((d,), None),
            ("embed", "unembed"): ((d, v), 1 / math.sqrt(d)),
            seg + ("attn_norm",): ((1, L, d), None),
            seg + ("attn", "wq"): ((1, L, d, h, hd), 1 / math.sqrt(d)),
            seg + ("attn", "wk"): ((1, L, d, kv, hd), 1 / math.sqrt(d)),
            seg + ("attn", "wv"): ((1, L, d, kv, hd), 1 / math.sqrt(d)),
            seg + ("attn", "wo"): ((1, L, h, hd, d), 1 / math.sqrt(h * hd)),
            seg + ("mlp_norm",): ((1, L, d), None),
            seg + ("mlp", "wi"): ((1, L, d, f), 1 / math.sqrt(d)),
            seg + ("mlp", "wg"): ((1, L, d, f), 1 / math.sqrt(d)),
            seg + ("mlp", "wo"): ((1, L, f, d), 1 / math.sqrt(f)),
        }

    @staticmethod
    def nest(flat: dict) -> dict:
        """``{path: x}`` to the nested tree; a ``"0"`` step is a list
        index."""
        out: dict = {}
        for path, x in flat.items():
            node = out
            for step in path[:-1]:
                node = node.setdefault(step, {})
            node[path[-1]] = x
        out["decoder"] = [out["decoder"]["0"]]
        return out

    # -- the plain reference ----------------------------------------------

    def logits(self, w, tokens, *, out_from: int, window: int = 0,
               kv_int8_from: int = -1, operand=None, rows: int = 4):
        """Teacher-forced logits ``(R, S - out_from, V)`` (float32, on the
        host) of ``tokens`` (R, S) at positions ``out_from ..``, one jitted
        layer at a time, in blocks of ``rows`` rows."""
        tokens = np.asarray(tokens, np.int32)
        seg = w["decoder"][0]
        out = []
        for lo in range(0, tokens.shape[0], rows):
            x = w["embed"]["embedding"][jnp.asarray(tokens[lo:lo + rows])]
            for i in range(self.layers):
                x = layer(x, seg, i, eps=self.norm_eps,
                          theta=self.rope_theta, window=window,
                          kv_int8_from=kv_int8_from, operand=operand)
            out.append(np.asarray(head(
                x[:, out_from:], w["embed"]["final_norm"],
                w["embed"]["unembed"], eps=self.norm_eps, operand=operand)))
            del x
        return np.concatenate(out, axis=0)

    # -- costs ------------------------------------------------------------

    @property
    def layer_matmul_params(self) -> int:
        """q, k, v and output projections and the three SwiGLU matrices."""
        qo = 2 * self.d * self.heads * self.head_dim
        kv = 2 * self.d * self.kv_heads * self.head_dim
        return qo + kv + 3 * self.d * self.ffn

    @property
    def matmul_params(self) -> int:
        """Weights a token multiplies with: every layer and the output head
        (the embedding is a lookup)."""
        return self.layers * self.layer_matmul_params + self.d * self.vocab

    @property
    def params(self) -> int:
        """Every parameter: matmul weights, the embedding, the norms."""
        norms = (2 * self.layers + 1) * self.d
        return self.matmul_params + self.vocab * self.d + norms

    def _kv_bytes_per_pos(self, batch: int, kv_dtype: str) -> int:
        """Keys and values of one position in every layer, int8 scales
        counted."""
        per_head = 2 * self.head_dim * DTYPE_BYTES[kv_dtype] \
            + (2 * 4 if kv_dtype == "int8" else 0)
        return self.layers * batch * self.kv_heads * per_head

    def decode_cost(self, *, batch: int, pos: int, window: int,
                    kv_dtype: str, dtype: str = "bfloat16") -> Cost:
        """One decode step of ``batch`` sequences whose new token sits at
        0-based position ``pos``: every weight read once at the compute
        dtype, the keys and values in reach read at the cache dtype (and
        the new ones written), int8 scales counted, the logits written."""
        ctx = attended(pos, window)
        flops = batch * (2 * self.matmul_params
                         + self.layers * 2 * 2 * self.heads * self.head_dim
                         * ctx)
        w = DTYPE_BYTES[dtype]
        weights = w * (self.matmul_params + (2 * self.layers + 1) * self.d) \
            + batch * self.d * w                           # embedding rows
        kv = self._kv_bytes_per_pos(batch, kv_dtype) * (ctx + 1)
        logits = batch * self.vocab * w
        acts = batch * self.d * w * self.layers * 4
        return Cost(flops=float(flops),
                    bytes=float(weights + kv + logits + acts))

    def prefill_cost(self, *, batch: int, prompt_len: int, window: int,
                     kv_dtype: str, dtype: str = "bfloat16") -> Cost:
        """One prefill of ``batch`` prompts of ``prompt_len`` tokens: every
        weight read once, each query's keys in reach, the cache written,
        the last position's logits."""
        p = prompt_len
        keys = sum(attended(q, window) for q in range(p))
        flops = batch * (2 * p * self.layers * self.layer_matmul_params
                         + self.layers * 2 * 2 * self.heads * self.head_dim
                         * keys
                         + 2 * self.d * self.vocab)
        w = DTYPE_BYTES[dtype]
        weights = w * (self.matmul_params + (2 * self.layers + 1) * self.d)
        cached = p if window <= 0 else min(p, window)
        cache = self._kv_bytes_per_pos(batch, kv_dtype) * cached
        acts = batch * p * self.d * w * self.layers * 2 + batch * p * self.d * w
        logits = batch * self.vocab * w
        return Cost(flops=float(flops),
                    bytes=float(weights + cache + acts + logits))


@partial(jax.jit,
         static_argnames=("eps", "theta", "window", "kv_int8_from", "operand"))
def layer(x, seg, i, *, eps, theta, window, kv_int8_from, operand):
    """Layer ``i`` of the stacked decoder segment ``seg`` on ``x``."""
    lw = _layer_weights(seg, i)
    h = ref.rmsnorm(x, lw["attn_norm"], eps)
    q = ref.rope(ref.mm("rsd,dhk->rshk", h, lw["wq"], operand), theta)
    k = ref.rope(ref.mm("rsd,dhk->rshk", h, lw["wk"], operand), theta)
    v = ref.mm("rsd,dhk->rshk", h, lw["wv"], operand)
    o = ref.attention(q, k, v, window=window, kv_int8_from=kv_int8_from,
                      operand=operand)
    x = x + ref.mm("rshk,hkd->rsd", o, lw["wo"], operand)
    h = ref.rmsnorm(x, lw["mlp_norm"], eps)
    g = jax.nn.silu(ref.mm("rsd,df->rsf", h, lw["wg"], operand))
    u = ref.mm("rsd,df->rsf", h, lw["wi"], operand)
    return x + ref.mm("rsf,fd->rsd", g * u, lw["wo_mlp"], operand)


@partial(jax.jit, static_argnames=("eps", "operand"))
def head(x, norm, unembed, *, eps, operand):
    """The final norm and the output head."""
    return ref.mm("rsd,dv->rsv", ref.rmsnorm(x, norm, eps), unembed, operand)


def _layer_weights(seg, i):
    """Layer ``i`` of the stacked decoder segment, sliced inside the jitted
    layer so that no second copy of the weights is held."""
    return {"attn_norm": seg["attn_norm"][0, i],
            "wq": seg["attn"]["wq"][0, i], "wk": seg["attn"]["wk"][0, i],
            "wv": seg["attn"]["wv"][0, i], "wo": seg["attn"]["wo"][0, i],
            "mlp_norm": seg["mlp_norm"][0, i],
            "wi": seg["mlp"]["wi"][0, i], "wg": seg["mlp"]["wg"][0, i],
            "wo_mlp": seg["mlp"]["wo"][0, i]}
