"""Smoke test of Compass's device paths on TPU chips.

    python chip_smoke.py             # one chip: model plane + simulation plane
    python chip_smoke.py --chips 4   # four chips: sharded serving vs one chip
    python chip_smoke.py --arch deepseek-moe-16b   # one chip: the MoE share

Run from the root of a checkout; everything is built from tracked files
and seeds.  The script refuses to run anywhere but a TPU (there is no CPU
fallback) and exits non-zero if any phase fails.  Its last line is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.

One chip, four phases:

- **serve**: ``repro.launch.serve`` with no flags but ``--arch`` —
  internlm2-1.8b at full width, batch 8, prompt 512, 32 new tokens,
  switching accurate -> fast -> accurate — checked for width, shapes,
  finite logits and both switches.  Then the same launcher code at
  float32 activations and the highest matmul precision, each rung's
  logits held to its own attention pattern's teacher-forced float32
  ``forward`` over the same tokens (``serve.rung_agreement``).  The
  accurate rung (prefill, and the steps after the switch back, which
  replay the tokens the fast rung served) meets the repo's decode-parity
  criteria (as in ``tests/test_kv_int8.py``): max |diff| <= 5% of max
  |logit| and correlation >= 0.999.  The fast rung (sliding window 256,
  int8 KV) is held to the window forward with full-precision KV: int8
  rounding moves this random-init model's logits by tens of percent, so
  the bound is a mean correlation >= 0.85 over its steps (0.90 measured
  on a v5e), and it must sit closer to its window forward than to full
  attention (0.07 measured).  Last, the bf16 launcher's rungs against
  the float32 forward at 1, 8 and 24 layers, a witness of the bf16 noise
  floor; at one layer each rung's mean correlation must reach 0.9.
- **validate**, **sweep_pipeline**, **replay_dag**: the jax Planner
  sweeps, each with ``backend="jax"`` against ``backend="numpy"`` on the
  same draws: ``Planner.validate`` on the RAG ladder of
  ``benchmarks/trace_replay_bench.py``, ``dag.sweep_pipeline`` on the
  8-stage tandem of ``benchmarks/dag_bench.py``, and
  ``traces.replay_dag`` over one day of that benchmark's diurnal trace.
  Tolerances: relative 1e-6 on mean latency/wait and exact p95s, the
  sketch resolution on streamed p95s, absolute 1e-3 on compliance.  An
  f32 evaluation misses the relative bound by orders of magnitude.

``--arch deepseek-moe-16b`` (one chip) runs one phase, **serve_moe**:
the serving plane on one expert-parallel rank's share of deepseek-moe-16b
(14 of 28 layers, routed experts 0-7 of 64 held, as
``chipbench/configs/deepseek-moe-16b.json`` cuts it) at batch 64, prompt
128, 64 new tokens, checked for finite logits and both switches, with its
call line and ``moe_held_assignments`` beside that count's expectation
under uniform routing.  Then the same plane at float32 and the highest
matmul precision, each rung held to the teacher-forced float32
``forward``, which computes the experts by the training path's dense
dispatch: the accurate rung by the decode-parity criteria above, the fast
rung (whose window does not bind at 192 positions) by the int8 floor
above, a mean correlation of at least 0.85 (0.917 measured on a v5e).

Four chips (``--chips 4``): the serving launcher on a (data, model) =
(1, 4) mesh and the check that its parameter shards sit on four devices;
then the comparison, at float32 as above, of the same seed served on the
1x4 mesh and on ``devices[0]``: prefill and first decode logits within
the decode-parity criteria, greedy tokens equal up to the first near tie.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "internlm2-1.8b"
MOE_ARCH = "deepseek-moe-16b"
MOE_CUT = dict(num_layers=14, experts_held=8)   # rank 0 of 8, layers 15-28
MOE_SHAPE = dict(batch=64, prompt_len=128, tokens=64, window=256)
LOGIT_REL_TOL = 0.05        # max |diff| / max |logit|, float32 decode parity
LOGIT_MIN_CORR = 0.999
INT8_MIN_MEAN_CORR = 0.85   # fast rung (int8 KV) vs its window forward
WITNESS_DEPTHS = (1, 8)     # cut depths of the bf16-vs-float32 witness
BF16_DEPTH1_MIN_MEAN_CORR = 0.9
SIM_REL_TOL = 1e-6          # mean latency / wait, exact p95
SIM_COMPLIANCE_TOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def grid_diff(name, ref, got, *, rel_tol=None, abs_tol=None):
    """Largest absolute and relative difference of two grids; raises past
    the tolerance that applies."""
    import numpy as np

    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    d = np.abs(ref - got)
    max_abs = float(d.max())
    max_rel = float((d / np.maximum(np.abs(ref), 1e-300)).max())
    log(f"  {name:<28} max_abs={max_abs!r} max_rel={max_rel!r}")
    if rel_tol is not None:
        check(max_rel <= rel_tol, f"{name}: relative diff {max_rel!r} > {rel_tol}")
    if abs_tol is not None:
        check(max_abs <= abs_tol, f"{name}: absolute diff {max_abs!r} > {abs_tol}")
    return max_abs, max_rel


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# model plane
# --------------------------------------------------------------------------


def report_serve(plane, res) -> None:
    import numpy as np

    for k, v in plane.compile_s.items():
        log(f"  compile {k}: {v:.3f} s")
    log(f"  prefill {res.prefill_s:.3f} s; decode {len(res.rungs)} tokens "
        f"({res.catch_up_steps} catch-up steps) {res.decode_s:.3f} s")
    log(f"  switches: {res.switches}")
    log(f"  tokens[:, 0]: {res.tokens[:, 0].tolist()}")
    check(np.isfinite(res.logits).all() and np.isfinite(res.prefill_logits).all(),
          "non-finite logits")
    directions = [(src, dst) for _, src, dst, _ in res.switches]
    check(directions == [("accurate", "fast"), ("fast", "accurate")],
          f"expected accurate -> fast -> accurate, got {directions}")


def serve_launcher(extra=()):
    """``repro.launch.serve`` as a user calls it (bf16 activations):
    (args, plane, result), checked for width, shapes and switches."""
    from repro.launch import serve

    args = serve.parse_args(["--arch", ARCH, *extra])
    plane, res = serve.run(args)
    cfg = plane.cfg
    width = (cfg.num_layers, cfg.d_model, cfg.vocab_size)
    check(width == (24, 2048, 92544), f"(layers, d_model, vocab) = {width}")
    check(res.logits.shape == (args.tokens, args.batch, cfg.vocab_size),
          f"logits shape {res.logits.shape}")
    report_serve(plane, res)
    return args, plane, res


def reference_plane(cfg, args, devices):
    """The launcher's serving plane at float32 activations under the
    highest matmul precision, on ``devices``: (plane, result).

    The bf16 rungs of this random-init model sit near a noise floor: its
    stacked layers are initialized with unit-variance weights, so each
    layer adds a residual update some 1e5 times the token embedding, and
    bf16 rounding of that update swamps the token signal.  Comparisons
    that are to mean anything run the same code at float32."""
    import dataclasses

    import jax

    from repro.launch import serve

    with jax.default_matmul_precision("highest"):
        plane = serve.ServingPlane(
            dataclasses.replace(cfg, dtype="float32"),
            mesh=serve.build_mesh(devices=devices), window=args.window,
            batch=args.batch, prompt_len=args.prompt_len,
            max_new=args.tokens)
        res = plane.generate(plane.prompt(args.batch, args.prompt_len),
                             args.tokens)
    for k, v in plane.compile_s.items():
        log(f"  compile float32 {k}: {v:.3f} s")
    return plane, res


def report_agreement(label, agree) -> None:
    for rung, by_ref in agree.items():
        for ref_rung, (rel, mn, mean) in by_ref.items():
            log(f"  {label} {rung} rung vs {ref_rung} forward: "
                f"max_rel={rel!r} min_corr={mn!r} mean_corr={mean!r}")


def phase_serve() -> None:
    import dataclasses

    import jax
    import numpy as np

    from repro.launch import serve

    args, plane, res = serve_launcher()
    cfg = plane.cfg
    prompt = np.asarray(plane.prompt(args.batch, args.prompt_len))
    forwards = serve.reference_forwards(cfg, args.window)
    with jax.default_matmul_precision("highest"):
        witness = {cfg.num_layers: serve.rung_agreement(
            forwards, plane.params, prompt, res)}
    del plane
    gc.collect()

    # the same launcher code at float32: each rung held to its own
    # attention pattern's teacher-forced forward over the prompt and every
    # decode input (the accurate rung's steps after the switch back
    # included), on two rows of the batch
    ref_plane, ref = reference_plane(cfg, args, jax.devices()[:1])
    check(ref.switches == res.switches, "float32 run switched differently")
    with jax.default_matmul_precision("highest"):
        agree = serve.rung_agreement(forwards, ref_plane.params, prompt, ref)
    del ref_plane
    gc.collect()
    report_agreement("float32", agree)
    rel, mn, _ = agree["accurate"]["accurate"]
    check(rel <= LOGIT_REL_TOL and mn >= LOGIT_MIN_CORR,
          "accurate decode disagrees with the forward reference")
    own, full = agree["fast"]["fast"], agree["fast"]["accurate"]
    check(own[2] >= INT8_MIN_MEAN_CORR and own[2] > full[2],
          "fast (window, int8 KV) decode disagrees with its window forward")

    # witness for the bf16 noise floor: the launcher's bf16 rungs against
    # the float32 forward, by depth
    for depth in WITNESS_DEPTHS:
        cut = dataclasses.replace(cfg, num_layers=depth)
        plane = serve.ServingPlane(
            cut, mesh=serve.build_mesh(devices=jax.devices()[:1]),
            window=args.window, batch=args.batch,
            prompt_len=args.prompt_len, max_new=args.tokens)
        res_d = plane.generate(plane.prompt(args.batch, args.prompt_len),
                               args.tokens)
        with jax.default_matmul_precision("highest"):
            witness[depth] = serve.rung_agreement(
                serve.reference_forwards(cut, args.window), plane.params,
                prompt, res_d)
        del plane
        gc.collect()
    for depth in sorted(witness):
        report_agreement(f"bf16 {depth} layers",
                         {r: {r: witness[depth][r][r]} for r in serve.RUNGS})
    for rung in serve.RUNGS:
        mean = witness[1][rung][rung][2]
        check(mean >= BF16_DEPTH1_MIN_MEAN_CORR,
              f"bf16 {rung} rung at one layer: mean correlation {mean!r}")


def phase_serve_moe() -> None:
    import argparse
    import dataclasses

    import jax
    import numpy as np

    from repro.launch import serve
    from repro.models.registry import get_config

    cfg = dataclasses.replace(get_config(MOE_ARCH), **MOE_CUT)
    args = argparse.Namespace(**MOE_SHAPE)
    devices = jax.devices()[:1]
    log(f"{MOE_ARCH}: {cfg.num_layers} layers, experts "
        f"{cfg.expert_first}-{cfg.expert_first + cfg.held_experts - 1} of "
        f"{cfg.num_experts} held; batch {args.batch}, prompt "
        f"{args.prompt_len}, {args.tokens} new tokens")
    plane = serve.ServingPlane(
        cfg, mesh=serve.build_mesh(devices=devices), window=args.window,
        batch=args.batch, prompt_len=args.prompt_len, max_new=args.tokens)
    prompt = plane.prompt(args.batch, args.prompt_len)
    res = plane.generate(prompt, args.tokens)
    report_serve(plane, res)
    log(f"  {serve.call_line(res)}")
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    steps = len(res.rungs) + res.catch_up_steps
    held = res.counters["moe_held_assignments"]
    expect = (args.batch * cfg.moe_top_k * moe_layers * steps
              * cfg.held_experts / cfg.num_experts)
    log(f"  moe_held_assignments {held}; uniform routing expects "
        f"{expect:.0f} (ratio {held / expect:.3f})")
    most = (args.batch * min(cfg.moe_top_k, cfg.held_experts) * moe_layers
            * steps)
    check(0 < held <= most, f"moe_held_assignments {held} outside (0, {most}]")
    del plane
    gc.collect()

    ref_plane, ref = reference_plane(cfg, args, devices)
    check(ref.switches == res.switches, "float32 run switched differently")
    with jax.default_matmul_precision("highest"):
        agree = serve.rung_agreement(serve.reference_forwards(cfg, args.window),
                                     ref_plane.params, np.asarray(prompt), ref)
    del ref_plane
    gc.collect()
    report_agreement("float32", agree)
    rel, mn, _ = agree["accurate"]["accurate"]
    check(rel <= LOGIT_REL_TOL and mn >= LOGIT_MIN_CORR,
          "accurate decode disagrees with the forward reference")
    # 192 positions: the 256 window does not bind, so the fast rung
    # differs from its forward by its int8 cache alone
    mean = agree["fast"]["fast"][2]
    check(mean >= INT8_MIN_MEAN_CORR,
          f"fast (int8 KV) decode: mean correlation {mean!r}")


def param_bytes_by_device(params):
    import jax

    out = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + shard.data.nbytes
    return out


def phase_serve_sharded() -> None:
    import jax
    import numpy as np

    from repro.launch.serve import logit_agreement

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    args, plane, _ = serve_launcher(["--mesh", "1x4"])
    per_dev = param_bytes_by_device(plane.params)
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(plane.params))
    log(f"  parameter bytes per device: {per_dev} (unsharded {total})")
    check(sorted(per_dev) == sorted(d.id for d in devices),
          f"parameter shards on devices {sorted(per_dev)}")
    check(max(per_dev.values()) < 0.5 * total, "parameters are not sharded")
    cfg = plane.cfg
    del plane
    gc.collect()

    # the comparison: the same seed at float32 on the 1x4 mesh and on
    # devices[0]
    sh_plane, sh = reference_plane(cfg, args, devices)
    log(f"  float32 1x4: decode {sh.decode_s:.3f} s")
    del sh_plane
    gc.collect()
    one_plane, one = reference_plane(cfg, args, devices[:1])
    log(f"  float32 one chip ({devices[0]}): decode {one.decode_s:.3f} s")
    del one_plane
    gc.collect()

    rel, corr = logit_agreement(one.prefill_logits, sh.prefill_logits)
    log(f"  prefill logits 1x4 vs 1 chip: max_rel={rel!r} corr={corr!r}")
    check(rel <= LOGIT_REL_TOL and corr >= LOGIT_MIN_CORR,
          "prefill logits disagree")
    same0 = one.inputs[0] == sh.inputs[0]
    check(same0.any(), "no row starts decoding from the same token")
    rel, corr = logit_agreement(one.logits[0][same0], sh.logits[0][same0])
    log(f"  first decode logits 1x4 vs 1 chip ({int(same0.sum())} rows): "
        f"max_rel={rel!r} corr={corr!r}")
    check(rel <= LOGIT_REL_TOL and corr >= LOGIT_MIN_CORR,
          "first decode logits disagree")
    # greedy tokens: a row may part ways only at a near tie of the
    # one-chip logits (top-2 gap within the logit tolerance)
    top2 = np.sort(one.logits, axis=-1)[..., -2:]
    gap = top2[..., 1] - top2[..., 0]                       # (n, B)
    tie = LOGIT_REL_TOL * np.abs(one.logits).max(axis=-1)   # (n, B)
    agree = one.tokens == sh.tokens
    first_div = [int(np.flatnonzero(~agree[:, b])[0]) if (~agree[:, b]).any()
                 else None for b in range(agree.shape[1])]
    log(f"  greedy tokens 1x4 vs 1 chip: {int(agree.sum())}/{agree.size} "
        f"equal; first divergence per row: {first_div}")
    for b, t in enumerate(first_div):
        if t is not None:
            check(gap[t, b] <= tie[t, b],
                  f"row {b} diverges at step {t} with top-2 gap "
                  f"{gap[t, b]!r} > {tie[t, b]!r}")


# --------------------------------------------------------------------------
# simulation plane
# --------------------------------------------------------------------------


def phase_validate() -> None:
    from benchmarks.trace_replay_bench import AMPLITUDE, BASE_UTIL, build_plan

    _, planner, plan = build_plan()
    base = BASE_UTIL / plan.table.policies[0].point.profile.mean
    rates = [base, base * (1.0 + AMPLITUDE / 2.0), base * (1.0 + AMPLITUDE)]
    kw = dict(arrival_rates_qps=rates, duration_s=900.0, replications=8, seed=0)
    ref, t_np = timed(planner.validate, plan, backend="numpy", **kw)
    got, t_j1 = timed(planner.validate, plan, backend="jax", **kw)
    _, t_j2 = timed(planner.validate, plan, backend="jax", **kw)
    log(f"  {ref.num_requests} requests; numpy {t_np:.3f} s, jax first "
        f"{t_j1:.3f} s (compile included), jax second {t_j2:.3f} s")
    grid_diff("validate mean_wait_s", ref.mean_wait_s, got.mean_wait_s,
              rel_tol=SIM_REL_TOL)
    grid_diff("validate p95_latency_s", ref.p95_latency_s, got.p95_latency_s,
              rel_tol=SIM_REL_TOL)
    grid_diff("validate slo_compliance", ref.slo_compliance,
              got.slo_compliance, abs_tol=SIM_COMPLIANCE_TOL)


def phase_sweep_pipeline() -> None:
    from benchmarks.dag_bench import SWEEP_CFG, SWEEP_STAGES
    from repro.serving.dag import StageSpec, WorkflowDAG, sweep_pipeline

    dag = WorkflowDAG.tandem([
        StageSpec(name=n, mean_s=tuple(m), p95_s=tuple(p), num_servers=c)
        for n, c, m, p in SWEEP_STAGES])
    rungs = [[0, 0, 0, 0, 0, k, k, 0] for k in range(5)]
    ref, t_np = timed(sweep_pipeline, dag, rungs, backend="numpy", **SWEEP_CFG)
    got, t_j1 = timed(sweep_pipeline, dag, rungs, backend="jax",
                      scan_impl="auto", **SWEEP_CFG)
    _, t_j2 = timed(sweep_pipeline, dag, rungs, backend="jax",
                    scan_impl="auto", **SWEEP_CFG)
    log(f"  {ref.num_requests} requests x {dag.num_stages} stages; numpy "
        f"{t_np:.3f} s, jax first {t_j1:.3f} s (compile included), jax "
        f"second {t_j2:.3f} s")
    grid_diff("sweep mean_latency_s", ref.mean_latency_s, got.mean_latency_s,
              rel_tol=SIM_REL_TOL)
    grid_diff("sweep p95_latency_s", ref.p95_latency_s, got.p95_latency_s,
              rel_tol=SIM_REL_TOL)
    grid_diff("sweep slo_compliance", ref.slo_compliance, got.slo_compliance,
              abs_tol=SIM_COMPLIANCE_TOL)


def phase_replay_dag() -> None:
    from benchmarks.dag_bench import STAGE_ORDER, _p95_from_cv
    from benchmarks.trace_replay_bench import (
        AMPLITUDE, BASE_UTIL, SLO_S, build_plan)
    from repro.serving.traces import diurnal_trace, replay_dag

    sur, _, plan = build_plan()
    fastest = plan.table.policies[0]
    base = BASE_UTIL / fastest.point.profile.mean
    trace = diurnal_trace(base, amplitude=AMPLITUDE, duration_s=86_400.0,
                          seed=11)
    parts = sur.stage_latencies_s(fastest.point.config)
    cv = sur.latency_cv(fastest.point.config)
    means = [parts[name] for name in STAGE_ORDER]
    p95s = [_p95_from_cv(m, cv) for m in means]
    kw = dict(slo_s=SLO_S, seed=11)
    ref, t_np = timed(replay_dag, trace, means, p95s, backend="numpy", **kw)
    got, t_j1 = timed(replay_dag, trace, means, p95s, backend="jax", **kw)
    _, t_j2 = timed(replay_dag, trace, means, p95s, backend="jax", **kw)
    e2e = ref.end_to_end
    log(f"  one day, {e2e.num_requests} requests x {len(means)} stages; numpy "
        f"{t_np:.3f} s, jax first {t_j1:.3f} s (compile included), jax "
        f"second {t_j2:.3f} s; engine {got.end_to_end.engine}")
    check(got.end_to_end.num_requests == e2e.num_requests, "request counts")
    pairs = list(zip((*STAGE_ORDER, "e2e"),
                     (*ref.stages, ref.end_to_end),
                     (*got.stages, got.end_to_end)))
    for field in ("mean_wait_s", "mean_latency_s"):
        grid_diff(f"replay {field}", [getattr(r, field) for _, r, _ in pairs],
                  [getattr(g, field) for _, _, g in pairs], rel_tol=SIM_REL_TOL)
    grid_diff("replay p95_latency_s", [r.p95_latency_s for _, r, _ in pairs],
              [g.p95_latency_s for _, _, g in pairs],
              abs_tol=min(r.p95_resolution_s for _, r, _ in pairs))
    grid_diff("replay slo_compliance", [e2e.slo_compliance],
              [got.end_to_end.slo_compliance], abs_tol=SIM_COMPLIANCE_TOL)


# --------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serving path and its "
                         "one-chip comparison")
    ap.add_argument("--arch", choices=(ARCH, MOE_ARCH), default=ARCH,
                    help=f"{MOE_ARCH}: run only its serving phase, on one "
                         f"chip")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        log(f"chip_smoke: no src/repro beside {__file__}; run it from the "
            f"root of a checkout")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax

    from repro.launch.cache import use_compile_cache

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    if dev.platform != "tpu":
        log(f"chip_smoke: needs a TPU, found platform {dev.platform!r}")
        return 2
    log(f"compile cache: {use_compile_cache()}")

    if args.arch == MOE_ARCH:
        phases = [("serve_moe", phase_serve_moe)]
    elif args.chips == 4:
        phases = [("serve_sharded", phase_serve_sharded)]
    else:
        phases = [("serve", phase_serve), ("validate", phase_validate),
                  ("sweep_pipeline", phase_sweep_pipeline),
                  ("replay_dag", phase_replay_dag)]
    failed = []
    for name, fn in phases:
        log(f"== phase {name}")
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            failed.append(name)
            log(f"== phase {name} FAILED")
        else:
            log(f"== phase {name} ok ({time.perf_counter() - t0:.3f} s)")
        gc.collect()
    if failed:
        log(f"chip_smoke: failed phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
