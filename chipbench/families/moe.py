"""The fine-grained MoE decoder (DeepSeek-MoE): sizes, parameter layout,
plain reference and step costs, for one expert-parallel rank's share of
each MoE layer.

The configuration file states the share: ``n_routed_experts`` experts
held here, from ``first_held_expert`` on, of the ``published`` count that
the router scores.  Leading dense layers (``first_k_dense_replace``) are
the dense family's layer; every later layer is attention followed by the
MoE FFN.

Parameter layout, the serving program's, spelled out here so that the
benchmark and not the program decides the values::

    embed:       embedding (V, d), final_norm (d,), unembed (d, V)
    first_dense: [ {attn_norm, attn: {wq, wk, wv, wo}, mlp_norm,
                    mlp: {wi, wg, wo}} ]          each stacked (1, dense, ...)
    decoder:     [ {attn_norm, attn: {...}, mlp_norm,
                    moe: {router (d, E), experts: {wi, wg (H, d, f),
                          wo (H, f, d)}, shared: {wi, wg (d, S f),
                          wo (S f, d)}}} ]        each stacked (1, moe, ...)

Each matrix gets standard deviation ``1 / sqrt(its own fan-in)`` (the
router's fan-in is d, an expert's output projection's f), as in
``dense.py``.

The reference (``Arch.logits``): the dense layers are ``dense.layer``;
each MoE layer is RMSNorm, attention as in ``dense.py``, then RMSNorm, a
float32 router softmax over all E experts, greedy top-k (rescaled to sum
1 only where ``norm_topk_prob``), the held experts' SwiGLU outputs
weighted by their top-k probability (zero where not chosen) and the
shared experts' SwiGLU, and the residual.  What the absent experts would
add is left out, as in the program.

The costs count the work a step needs, whatever dispatch computes it:
routed operations are ``tokens x k x H / E`` expert passes; routed bytes
are the held experts a step is expected to touch, ``H (1 - ((E - k) /
E) ** tokens)`` under uniform routing, at the compute dtype.  Router,
shared experts, attention, the dense layers, the cache, the activations
and the logits are counted as ``dense.py`` counts them.
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
from .dense import head
from .dense import layer as dense_layer


@dataclass(frozen=True)
class Arch:
    """The sizes of one MoE decoder share, as its configuration file
    states."""

    layers: int             # all layers held here, dense ones included
    dense_layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    dense_ffn: int
    expert_ffn: int
    experts: int            # routed experts the router scores
    held: int               # of them, held here
    first_held: int
    shared: int
    top_k: int
    norm_topk: bool
    vocab: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        published = c.get("published", {})
        return cls(layers=c["num_hidden_layers"],
                   dense_layers=c["first_k_dense_replace"],
                   d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   dense_ffn=c["intermediate_size"],
                   expert_ffn=c["moe_intermediate_size"],
                   experts=published.get("n_routed_experts",
                                         c["n_routed_experts"]),
                   held=c["n_routed_experts"],
                   first_held=c.get("first_held_expert", 0),
                   shared=c["n_shared_experts"],
                   top_k=c["num_experts_per_tok"],
                   norm_topk=bool(c["norm_topk_prob"]),
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]))

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    # -- the program ------------------------------------------------------

    @staticmethod
    def program_config(config: dict):
        """The program's model configuration with the sizes of the cell's
        configuration file; every size the file states has to hold."""
        from repro.models.registry import get_config

        a = Arch.from_config(config)
        if config.get("scoring_func", "softmax") != "softmax" \
                or config.get("moe_layer_freq", 1) != 1:
            raise ValueError(f"{config['name']}: the program routes by a "
                             f"softmax in every layer after the dense ones")
        base = get_config(config["program_arch"])
        sizes = dict(num_layers=a.layers, d_model=a.d, num_heads=a.heads,
                     num_kv_heads=a.kv_heads, head_dim=a.head_dim,
                     d_ff=a.expert_ffn, dense_ff=a.dense_ffn,
                     first_dense_layers=a.dense_layers,
                     num_experts=a.experts, experts_held=a.held,
                     expert_first=a.first_held,
                     num_shared_experts=a.shared, moe_top_k=a.top_k,
                     norm_topk_prob=a.norm_topk, vocab_size=a.vocab,
                     rope_theta=a.rope_theta, norm_eps=a.norm_eps,
                     dtype=config["serving"]["dtype"],
                     param_dtype=config["serving"]["param_dtype"],
                     tied_embeddings=bool(config["tie_word_embeddings"]))
        cfg = dataclasses.replace(base, **sizes)
        if cfg.family != "moe" or cfg.act != "swiglu":
            raise ValueError(f"{config['name']}: the program's model is "
                             f"{cfg.family}/{cfg.act}, the file states "
                             f"moe/swiglu")
        return cfg

    # -- parameters -------------------------------------------------------

    def _attention_specs(self, seg, n) -> dict:
        d, h, kv, hd = self.d, self.heads, self.kv_heads, self.head_dim
        return {
            seg + ("attn_norm",): ((1, n, d), None),
            seg + ("attn", "wq"): ((1, n, d, h, hd), 1 / math.sqrt(d)),
            seg + ("attn", "wk"): ((1, n, d, kv, hd), 1 / math.sqrt(d)),
            seg + ("attn", "wv"): ((1, n, d, kv, hd), 1 / math.sqrt(d)),
            seg + ("attn", "wo"): ((1, n, h, hd, d), 1 / math.sqrt(h * hd)),
            seg + ("mlp_norm",): ((1, n, d), None),
        }

    def leaf_specs(self) -> dict:
        """``{path: (shape, std)}``; std ``None`` marks a norm scale."""
        d, v, f, n = self.d, self.vocab, self.expert_ffn, self.moe_layers
        sf, hf = self.shared * f, self.held
        specs = {
            ("embed", "embedding"): ((v, d), 1.0),
            ("embed", "final_norm"): ((d,), None),
            ("embed", "unembed"): ((d, v), 1 / math.sqrt(d)),
        }
        if self.dense_layers:
            seg, nd, F = ("first_dense", "0"), self.dense_layers, self.dense_ffn
            specs.update(self._attention_specs(seg, nd))
            specs.update({
                seg + ("mlp", "wi"): ((1, nd, d, F), 1 / math.sqrt(d)),
                seg + ("mlp", "wg"): ((1, nd, d, F), 1 / math.sqrt(d)),
                seg + ("mlp", "wo"): ((1, nd, F, d), 1 / math.sqrt(F)),
            })
        seg = ("decoder", "0")
        moe = seg + ("moe",)
        specs.update(self._attention_specs(seg, n))
        specs.update({
            moe + ("router",): ((1, n, d, self.experts), 1 / math.sqrt(d)),
            moe + ("experts", "wi"): ((1, n, hf, d, f), 1 / math.sqrt(d)),
            moe + ("experts", "wg"): ((1, n, hf, d, f), 1 / math.sqrt(d)),
            moe + ("experts", "wo"): ((1, n, hf, f, d), 1 / math.sqrt(f)),
        })
        if self.shared:
            specs.update({
                moe + ("shared", "wi"): ((1, n, d, sf), 1 / math.sqrt(d)),
                moe + ("shared", "wg"): ((1, n, d, sf), 1 / math.sqrt(d)),
                moe + ("shared", "wo"): ((1, n, sf, d), 1 / math.sqrt(sf)),
            })
        return specs

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
        for seg in ("first_dense", "decoder"):
            if seg in out:
                out[seg] = [out[seg]["0"]]
        return out

    # -- the plain reference ----------------------------------------------

    def logits(self, w, tokens, *, out_from: int, window: int = 0,
               kv_int8_from: int = -1, operand=None, rows: int = 4):
        """Teacher-forced logits ``(R, S - out_from, V)`` (float32, on the
        host) of ``tokens`` (R, S) at positions ``out_from ..``, one jitted
        layer at a time, in blocks of ``rows`` rows."""
        tokens = np.asarray(tokens, np.int32)
        kw = dict(eps=self.norm_eps, theta=self.rope_theta, window=window,
                  kv_int8_from=kv_int8_from, operand=operand)
        out = []
        for lo in range(0, tokens.shape[0], rows):
            x = w["embed"]["embedding"][jnp.asarray(tokens[lo:lo + rows])]
            for i in range(self.dense_layers):
                x = dense_layer(x, w["first_dense"][0], i, **kw)
            for i in range(self.moe_layers):
                x = layer(x, w["decoder"][0], i, top_k=self.top_k,
                          first=self.first_held, norm_topk=self.norm_topk,
                          **kw)
            out.append(np.asarray(head(
                x[:, out_from:], w["embed"]["final_norm"],
                w["embed"]["unembed"], eps=self.norm_eps, operand=operand)))
            del x
        return np.concatenate(out, axis=0)

    # -- costs ------------------------------------------------------------

    @property
    def attention_params(self) -> int:
        """q, k, v and output projections of one layer."""
        return 2 * self.d * self.head_dim * (self.heads + self.kv_heads)

    @property
    def expert_params(self) -> int:
        """One routed expert's three SwiGLU matrices."""
        return 3 * self.d * self.expert_ffn

    @property
    def moe_layer_dense_params(self) -> int:
        """What every token of a MoE layer multiplies with: attention, the
        router and the shared experts."""
        return (self.attention_params + self.d * self.experts
                + self.shared * self.expert_params)

    @property
    def dense_layer_params(self) -> int:
        return self.attention_params + 3 * self.d * self.dense_ffn

    @property
    def dense_matmul_params(self) -> int:
        """Weights every token multiplies with: the dense layers, each MoE
        layer's attention, router and shared experts, and the output head
        (the embedding is a lookup)."""
        return (self.dense_layers * self.dense_layer_params
                + self.moe_layers * self.moe_layer_dense_params
                + self.d * self.vocab)

    @property
    def params(self) -> int:
        """Every parameter held here: the held experts, the rest of the
        matmul weights, the embedding, the norms."""
        norms = (2 * self.layers + 1) * self.d
        return (self.dense_matmul_params
                + self.moe_layers * self.held * self.expert_params
                + self.vocab * self.d + norms)

    def routed_passes(self, tokens: int) -> float:
        """Expert passes the held experts make for ``tokens`` tokens of one
        layer: each token's k assignments, the held share of them."""
        return tokens * self.top_k * self.held / self.experts

    def touched(self, tokens: int) -> float:
        """Held experts that ``tokens`` tokens of one layer are expected to
        route to under uniform routing: an expert escapes one token with
        probability (E - k) / E."""
        escape = (self.experts - self.top_k) / self.experts
        return self.held * (1.0 - escape ** tokens)

    def _kv_bytes_per_pos(self, batch: int, kv_dtype: str) -> int:
        """Keys and values of one position in every layer, int8 scales
        counted."""
        per_head = 2 * self.head_dim * DTYPE_BYTES[kv_dtype] \
            + (2 * 4 if kv_dtype == "int8" else 0)
        return self.layers * batch * self.kv_heads * per_head

    def _weight_bytes(self, tokens: int, w: int) -> float:
        """Weights one step of ``tokens`` tokens reads once: the dense
        ones, the held experts it is expected to touch, the norms."""
        return w * (self.dense_matmul_params
                    + self.moe_layers * self.touched(tokens)
                    * self.expert_params
                    + (2 * self.layers + 1) * self.d)

    def _attention_flops(self, keys: int) -> int:
        """Scores and values of every layer for ``keys`` query-key pairs."""
        return self.layers * 2 * 2 * self.heads * self.head_dim * keys

    def decode_cost(self, *, batch: int, pos: int, window: int,
                    kv_dtype: str, dtype: str = "bfloat16") -> Cost:
        """One decode step of ``batch`` sequences whose new token sits at
        0-based position ``pos``: the weights a step touches read once at
        the compute dtype, the keys and values in reach read at the cache
        dtype (and the new ones written), int8 scales counted, the logits
        written."""
        ctx = attended(pos, window)
        flops = (batch * (2 * self.dense_matmul_params
                          + self._attention_flops(ctx))
                 + 2 * self.expert_params * self.moe_layers
                 * self.routed_passes(batch))
        w = DTYPE_BYTES[dtype]
        weights = self._weight_bytes(batch, w) + batch * self.d * w
        kv = self._kv_bytes_per_pos(batch, kv_dtype) * (ctx + 1)
        logits = batch * self.vocab * w
        acts = batch * self.d * w * self.layers * 4
        return Cost(flops=float(flops),
                    bytes=float(weights + kv + logits + acts))

    def prefill_cost(self, *, batch: int, prompt_len: int, window: int,
                     kv_dtype: str, dtype: str = "bfloat16") -> Cost:
        """One prefill of ``batch`` prompts of ``prompt_len`` tokens: the
        weights touched read once, each query's keys in reach, the cache
        written, the last position's logits."""
        p = prompt_len
        tokens = batch * p
        keys = sum(attended(q, window) for q in range(p))
        body = self.dense_matmul_params - self.d * self.vocab
        flops = (batch * (2 * p * body + self._attention_flops(keys)
                          + 2 * self.d * self.vocab)
                 + 2 * self.expert_params * self.moe_layers
                 * self.routed_passes(tokens))
        w = DTYPE_BYTES[dtype]
        weights = self._weight_bytes(tokens, w)
        cached = p if window <= 0 else min(p, window)
        cache = self._kv_bytes_per_pos(batch, kv_dtype) * cached
        acts = tokens * self.d * w * self.layers * 2 + tokens * self.d * w
        logits = batch * self.vocab * w
        return Cost(flops=float(flops),
                    bytes=float(weights + cache + acts + logits))


def routing(h, router, *, top_k, norm_topk, operand):
    """The router's top-k probabilities and expert indices ``(R, S, k)``:
    a float32 softmax over all experts, greedy top-k, rescaled to sum 1
    only where ``norm_topk``."""
    probs = jax.nn.softmax(ref.mm("rsd,de->rse", h, router, operand), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top = top / top.sum(-1, keepdims=True)
    return top, idx


def moe_ffn(h, mw, *, top_k, first, norm_topk, operand):
    """The MoE FFN of the held experts and the shared ones on ``h`` (R, S,
    d): every held expert on every token, weighted by its top-k
    probability, zero where the router did not choose it."""
    top, idx = routing(h, mw["router"], top_k=top_k, norm_topk=norm_topk,
                       operand=operand)
    held = mw["experts"]["wi"].shape[0]
    chosen = idx[..., None] == first + jnp.arange(held)         # (R, S, k, H)
    gate = jnp.where(chosen, top[..., None], 0.0).sum(-2)       # (R, S, H)
    ew = mw["experts"]
    g = jax.nn.silu(ref.mm("rsd,hdf->rshf", h, ew["wg"], operand))
    u = ref.mm("rsd,hdf->rshf", h, ew["wi"], operand)
    y = ref.mm("rshf,hfd->rshd", g * u, ew["wo"], operand)
    out = (y * gate[..., None]).sum(2)
    if "shared" in mw:
        sw = mw["shared"]
        g = jax.nn.silu(ref.mm("rsd,df->rsf", h, sw["wg"], operand))
        u = ref.mm("rsd,df->rsf", h, sw["wi"], operand)
        out = out + ref.mm("rsf,fd->rsd", g * u, sw["wo"], operand)
    return out


def attend(x, lw, *, eps, theta, window, kv_int8_from, operand):
    """``x`` after the attention half of a layer (``lw``: one layer's
    weights), as ``dense.py`` computes it."""
    aw = lw["attn"]
    h = ref.rmsnorm(x, lw["attn_norm"], eps)
    q = ref.rope(ref.mm("rsd,dhk->rshk", h, aw["wq"], operand), theta)
    k = ref.rope(ref.mm("rsd,dhk->rshk", h, aw["wk"], operand), theta)
    v = ref.mm("rsd,dhk->rshk", h, aw["wv"], operand)
    o = ref.attention(q, k, v, window=window, kv_int8_from=kv_int8_from,
                      operand=operand)
    return x + ref.mm("rshk,hkd->rsd", o, aw["wo"], operand)


@partial(jax.jit, static_argnames=("eps", "theta", "window", "kv_int8_from",
                                   "operand", "top_k", "first", "norm_topk"))
def layer(x, seg, i, *, eps, theta, window, kv_int8_from, operand, top_k,
          first, norm_topk):
    """MoE layer ``i`` of the stacked decoder segment ``seg`` on ``x``."""
    lw = jax.tree.map(lambda a: a[0, i], seg)   # sliced inside the jit
    x = attend(x, lw, eps=eps, theta=theta, window=window,
               kv_int8_from=kv_int8_from, operand=operand)
    h = ref.rmsnorm(x, lw["mlp_norm"], eps)
    return x + moe_ffn(h, lw["moe"], top_k=top_k, first=first,
                       norm_topk=norm_topk, operand=operand)
