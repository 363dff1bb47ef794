"""Perf-iteration harness (§Perf hillclimbing).

Runs one (arch x shape) case with a named VARIANT — a set of config /
sharding overrides — re-derives the three roofline terms, and appends the
record to experiments/perf_iterations.jsonl.  ``--attribute`` additionally
prints the largest collective instructions (bytes x trip count) so the
dominant term can be attributed to specific tensors before choosing the next
change.

MUST run as its own process (:func:`main` forces 512 host devices before
jax initializes a backend):

    PYTHONPATH=src python -m repro.launch.perf --arch minitron-4b \
        --shape train_4k --variant baseline --attribute
"""

import argparse
import dataclasses
import json
import os
import re
import time
from typing import Dict, Optional

import numpy as np

from ..configs import INPUT_SHAPES
from ..models.common import ModelConfig
from .dryrun import BIG_ARCHS, HOST_DEVICE_FLAGS, effective_config, lower_case
from .analytic import step_costs
from .roofline import (
    _multipliers,
    _split_computations,
    _SHAPE_RE,
    _shape_bytes,
    analyze,
    model_flops_for,
    parse_collectives,
)

# ---------------------------------------------------------------------------
# named variants: config overrides per hillclimb iteration
# ---------------------------------------------------------------------------

VARIANTS: Dict[str, Dict] = {
    # "_planner" is passed to ShardingPlanner, everything else to the config.
    # fsdp_vocab=True reproduces the committed baseline's sharding.
    "baseline": {"_planner": {"fsdp_vocab": True}, "act_hints": False},
    "hints_only": {"_planner": {"fsdp_vocab": True}},
    # Pair A: deepseek-moe-16b x train_4k (compute-bound, useful=0.17)
    "moe_gshard": {"moe_impl": "gshard", "_planner": {"fsdp_vocab": True},
                   "act_hints": False},
    "moe_gshard_cf1": {"moe_impl": "gshard", "capacity_factor": 1.0,
                       "_planner": {"fsdp_vocab": True}, "act_hints": False},
    "moe_gshard_sharded_ce": {"moe_impl": "gshard", "sharded_ce": True},
    "moe_gshard_cf1_sharded_ce": {"moe_impl": "gshard", "capacity_factor": 1.0,
                                  "sharded_ce": True},
    # Pair B: minitron-4b x train_4k (collective-bound)
    #   sharded cross-entropy is a CODE change (models/layers.py), toggled via
    #   the config flag; no_vocab_fsdp is a ShardingPlanner rule change.
    "sharded_ce_only": {"sharded_ce": True, "_planner": {"fsdp_vocab": True},
                        "act_hints": False},
    "no_vocab_fsdp": {},
    "sharded_ce_no_vocab_fsdp": {"sharded_ce": True},
    # Pair C: llama3-405b x decode_32k (memory-bound)
    "kv_int8": {"kv_cache_dtype": "int8"},
    "window_8k": {"sliding_window": 8192},
    "window_8k_kv_int8": {"sliding_window": 8192, "kv_cache_dtype": "int8"},
    "serve_bf16": {"param_dtype": "bfloat16"},
    "serve_bf16_kv_int8": {"param_dtype": "bfloat16", "kv_cache_dtype": "int8"},
    "serve_bf16_kv_int8_window8k": {"param_dtype": "bfloat16",
                                    "kv_cache_dtype": "int8",
                                    "sliding_window": 8192},
}


def attribute_collectives(hlo_text: str, top: int = 12) -> list:
    """Top collective instructions by (bytes x trip count)."""
    comps = _split_computations(hlo_text)
    mults = _multipliers(comps)
    rows = []
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    for comp_name, lines in comps.items():
        mult = mults.get(comp_name, 1)
        for raw in lines:
            stripped = raw.strip()
            m = re.match(r"^(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(.*)$", stripped)
            if not m:
                continue
            rhs = m.group(2)
            kind = next((c for c in kinds if re.search(rf"\b{c}(-start)?\(", rhs)), None)
            if kind is None:
                continue
            result_part = rhs.split(kind)[0]
            shapes = _SHAPE_RE.findall(result_part)
            size = sum(_shape_bytes(d, dims) for d, dims in shapes)
            rows.append(
                {
                    "kind": kind,
                    "bytes": size * mult,
                    "mult": mult,
                    "shape": " ".join(f"{d}[{s}]" for d, s in shapes),
                    "comp": comp_name[:40],
                }
            )
    rows.sort(key=lambda r: -r["bytes"])
    return rows[:top]


def run_variant(arch: str, shape_name: str, variant: str,
                *, attribute: bool = False) -> Dict:
    overrides = dict(VARIANTS[variant])
    planner_kwargs = overrides.pop("_planner", None)
    cfg = effective_config(arch, shape_name)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    shape = INPUT_SHAPES[shape_name]
    t0 = time.time()
    lowered, meta = lower_case(arch, shape_name, cfg=cfg,
                               planner_kwargs=planner_kwargs)
    compiled = lowered.compile()
    t_total = time.time() - t0

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    dec_len = None
    if cfg.family == "audio":
        dec_len = max(1, shape.seq_len // cfg.decoder_len_ratio)
    mf = model_flops_for(cfg, shape.kind, shape.seq_len, shape.global_batch,
                         decoder_len=dec_len)
    costs = step_costs(
        cfg, shape.kind, shape.seq_len, shape.global_batch,
        opt_state_dtype_bytes=2 if arch in BIG_ARCHS else 4,
    )
    report = analyze(
        arch=arch, shape=shape_name, mesh_name=meta["mesh"], chips=meta["chips"],
        cost=dict(cost), hlo_text=hlo, model_flops=mf,
        analytic_flops=costs.flops, analytic_bytes=costs.hbm_bytes,
        compile_s=t_total, note=f"variant={variant}",
    )
    rec = dataclasses.asdict(report)
    rec["variant"] = variant
    rec["kind"] = shape.kind
    if attribute:
        rec["top_collectives"] = attribute_collectives(hlo)
    return rec


def main() -> None:
    os.environ["XLA_FLAGS"] = HOST_DEVICE_FLAGS
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline",
                    help=",".join(VARIANTS))
    ap.add_argument("--attribute", action="store_true")
    ap.add_argument("--out", default="experiments/perf_iterations.jsonl")
    args = ap.parse_args()

    for variant in args.variant.split(","):
        rec = run_variant(args.arch, args.shape, variant, attribute=args.attribute)
        print(
            f"{args.arch} x {args.shape} [{variant}]: "
            f"compute={rec['compute_s'] * 1e3:.2f}ms "
            f"memory={rec['memory_s'] * 1e3:.2f}ms "
            f"collective={rec['collective_s'] * 1e3:.2f}ms "
            f"bottleneck={rec['bottleneck']} useful={rec['useful_flops_ratio']:.3f}"
        )
        if args.attribute:
            for r in rec["top_collectives"]:
                print(
                    f"    {r['kind']:18s} {r['bytes'] / 1e9:8.2f}GB  x{r['mult']:<5d}"
                    f" {r['shape']}  in {r['comp']}"
                )
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
