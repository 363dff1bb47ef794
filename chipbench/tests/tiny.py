"""A tiny cell for runs on the CPU: the internlm2 layout at the widths of
the program's reduced configuration (2 layers, d 256, GQA 4 over 2 heads
of 64, FFN 512, vocab 512), a fast rung with a window of 16."""

from chipbench.spec import Cell, metric_reader, Metric

CONFIG = {
    "name": "tiny", "family": "dense", "program_arch": "internlm2-1.8b",
    "num_hidden_layers": 2, "hidden_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
    "hidden_act": "silu", "tie_word_embeddings": False, "bias": False,
    "serving": {"dtype": "bfloat16", "param_dtype": "float32",
                "accurate": {"sliding_window": 0,
                             "kv_cache_dtype": "bfloat16"},
                "fast": {"sliding_window": 16, "kv_cache_dtype": "int8"}},
}
TRAFFIC = {"name": "tiny", "loop": "closed", "clients": 1, "batch": 4,
           "prompt_len": 32, "new_tokens": 12, "token_ids": "uniform",
           "schedule": [["accurate", 4], ["fast", 4], ["accurate", 4]]}


# Limits for the tiny cell on the CPU, set from ``tools/calibrate.py
# --tiny`` (6 seeds): the bf16 program's widest gaps read at most 0.0166
# (accurate) and 0.0220 (fast); the fp8 control's accurate gap read at
# least 0.0769 on every seed.
LIMITS = {"gap_accurate": {"limit": 0.045}, "gap_fast": {"limit": 0.045}}


def cell(limits=None, e2e=("setup_s", "tok_s"),
         per_layer=()):
    limits = limits or LIMITS
    return Cell(name="tiny.tiny", chips=1, config=CONFIG, traffic=TRAFFIC,
                limits=limits,
                end_to_end=[Metric(n, "x", metric_reader(n)) for n in e2e],
                per_layer=[Metric(n, "x", metric_reader(n))
                           for n in per_layer])
