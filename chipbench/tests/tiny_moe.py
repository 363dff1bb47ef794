"""A tiny MoE cell for runs on the CPU: the deepseek-moe-16b layout at
small widths (a dense first layer and 2 MoE layers, d 64, 4 MHA heads of
16, dense FFN 128, 16 routed experts of width 32 of which experts 4-7
are held, top-4 unscaled, 1 shared expert, vocab 512), a fast rung with
a window of 16 and int8 KV."""

from chipbench.spec import Cell, Metric, metric_reader
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-moe", "family": "moe", "program_arch": "deepseek-moe-16b",
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "first_held_expert": 4, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "scoring_func": "softmax", "norm_topk_prob": False, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "tie_word_embeddings": False,
    "published": {"n_routed_experts": 16},
    "serving": tiny.CONFIG["serving"],
}
TRAFFIC = tiny.TRAFFIC

# Limits for the tiny MoE cell on the CPU, set from the bf16 program's
# widest gaps and the fp8 control's over 6 seeds (as ``tools/calibrate.py``
# reads them): the program read at most 0.0268 (accurate) and 0.0059
# (fast); the control's widest gap read at least 0.174 on every seed.
LIMITS = {"gap_accurate": {"limit": 0.08}, "gap_fast": {"limit": 0.08}}


def cell(limits=None, e2e=("setup_s", "tok_s"), per_layer=()):
    return Cell(name="tiny-moe.tiny", chips=1, config=CONFIG,
                traffic=TRAFFIC, limits=limits or LIMITS,
                end_to_end=[Metric(n, "x", metric_reader(n)) for n in e2e],
                per_layer=[Metric(n, "x", metric_reader(n))
                           for n in per_layer])
