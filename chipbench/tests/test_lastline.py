"""The last line of a run holds the contract's keys and ``check`` last."""

import json

from chipbench import harness, run
from chipbench.tests import tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_last_line_keys(tmp_path):
    import jax

    result, compared = harness.run_cell(
        tiny.cell(), seed=3, seconds=0.4, trace=False,
        devices=jax.devices()[:1], t_process=0.0, root=tmp_path)
    line = json.loads(run.result_line(result, compared))
    assert list(line) == KEYS + ["check"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"setup_s", "tok_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in line["check"].values():
        assert set(c) == {"value", "limit"}
    assert line["attempted"] > 0 and line["failed"] == 0

