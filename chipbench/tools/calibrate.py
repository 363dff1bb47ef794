"""Readings that the correctness limits of a cell are set from.

    python3 chipbench/tools/calibrate.py --workload internlm2-1.8b.rag \\
        --seeds 11,12,13 --control 3 --calls 2

One process: the cell's plane is built and compiled once; then for each
seed it gets that seed's weights, serves ``--calls`` calls of the seed's
prompts through ``ServingPlane.generate`` (the window's own path, at the
cell's batch and lengths), and the sample a run would draw is held to the
reference (``check.readings``).  On the first ``--control`` seeds the
control is read too: the reference in fp8 put in the program's place.

Each seed prints one line; the last line is a JSON summary: per number
the lower reading (the largest the program gave) and the upper one (the
smallest the control gave).  ``--tiny`` runs the CPU test cell instead.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--calls", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from chipbench import harness, spec
    from repro.launch.cache import use_compile_cache

    if args.tiny:
        from chipbench.tests import tiny
        cell = tiny.cell()
    else:
        cell = spec.load_cell(args.workload)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}; cell {cell.name}",
          flush=True)
    t = cell.traffic
    b, p, n = int(t["batch"]), int(t["prompt_len"]), int(t["new_tokens"])
    seeds = [int(x) for x in args.seeds.split(",")]
    plane = arch = None
    rows = []
    for k, seed in enumerate(seeds):
        if plane is None:
            plane, arch = harness.build_plane(cell, jax.devices()[:cell.chips],
                                              seed)
        else:
            harness.weights.install(plane, arch, seed)
        host = harness.prompts(seed, args.calls, b, p, arch.vocab)
        answers = [harness.answer(plane.generate(
            jax.device_put(x, plane.tokens_sh), n)) for x in host]
        got = harness.collect(answers, host, t)
        del answers
        plane.params = None                 # room for the reference
        gc.collect()
        r = harness.compare(cell, arch, seed, got, control=k < args.control)
        r.update(seed=seed, failed=got.failed, off_schedule=got.off_schedule)
        rows.append(r)
        print(json.dumps(r), flush=True)
    names = sorted(k for k in rows[0] if k.startswith("gap_"))
    summary = {"cell": cell.name, "device": dev.device_kind,
               "seeds": seeds, "calls": args.calls}
    for name in names:
        low = max(r[name] for r in rows)
        ctl = [r[f"control.{name}"] for r in rows if f"control.{name}" in r]
        summary[name] = {"lower": low, "upper": min(ctl) if ctl else None,
                         "program": [r[name] for r in rows],
                         "control": ctl}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
