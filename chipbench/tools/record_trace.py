"""Record the small trace that ``tests/test_trace.py`` reduces.

    python3 chipbench/tools/record_trace.py OUT_DIR

On a TPU: one traced run of the tiny test cell (the internlm2 layout at
the program's reduced widths, batch 4, prompt 32, 12 new tokens) through
the harness, its trace kept under ``OUT_DIR``; prints the reading and the
dispatches the harness recorded, which the test holds the reduction to.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import harness, trace
    from chipbench.tests import tiny

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    steps = []
    real = harness.Tracer.__init__

    def keep_steps(self, *a, **kw):
        real(self, *a, **kw)
        steps.append(self.steps)

    harness.Tracer.__init__ = keep_steps
    seen = {}
    real_read = trace.stop_and_read

    def keep_reading(trace_dir, modules, **kw):
        seen["modules"] = modules
        seen["reading"] = real_read(trace_dir, modules, **kw)
        return seen["reading"]

    trace.stop_and_read = keep_reading
    result, _ = harness.run_cell(
        tiny.cell(per_layer=("decode_ms.accurate", "idle_share")), seed=1,
        seconds=0.25, trace=True, devices=jax.devices()[:1], t_process=T0,
        root=Path(out), keep_trace=True)
    path = trace.newest_xplane(Path(out))
    print(json.dumps({"xplane": str(path), "bytes": path.stat().st_size,
                      "result": result, "modules": seen["modules"],
                      "reading": dataclasses.asdict(seen["reading"]),
                      "steps": [dataclasses.astuple(s) for s in steps[0]]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
