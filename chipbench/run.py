"""Run one cell of the chip benchmark, on the machine it is started on.

    python3 chipbench/run.py --workload internlm2-1.8b.rag --seed 7 \\
        --seconds 51 --trace 0

from the root of a checkout.  It needs a TPU with at least the chips the
cell asks for; on anything else (a CPU, too few chips) it exits 3 and
prints no result.  Without the program beside it (``src/``) it exits 2.

Standard error carries the run's account; its last lines are the numbers
compared, each beside its limit.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``--trace 1`` also ``breakdown``), and ``check`` last.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(result: dict, compared: dict) -> str:
    """The last line: the result's keys, then ``check`` last."""
    return json.dumps({**result, "check": compared})


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no program (src/repro) in {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from chipbench import harness, spec

    cell = spec.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} TPU chip(s); found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    result, compared = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices[:cell.chips], t_process=T_PROCESS, root=ROOT)
    for name, c in compared.items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(result_line(result, compared), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
