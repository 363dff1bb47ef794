"""The trace reduction on a small trace recorded on a TPU v5e.

``testdata/tiny.xplane.pb`` is one traced run of the tiny cell through
the harness (``tools/record_trace.py``); ``testdata/tiny.json`` holds
what the harness recorded with it (the module names of the plane's
programs and the dispatches in order), the window and busy time the chip
run read, and the reading this reduction gives, which the test holds it
to.
"""

import json
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.harness import Step

DATA = Path(__file__).resolve().parents[1] / "testdata"
LABELS = {"prompt", "call", "prefill", "decode.accurate", "decode.fast",
          "catchup", "handoff", "untraced host"}


@pytest.fixture(scope="module")
def recorded():
    meta = json.loads((DATA / "tiny.json").read_text())
    steps = [Step(*s) for s in meta["steps"]]
    reading = trace.read(DATA / "tiny.xplane.pb", meta["modules"], steps,
                         n_devices=1)
    return meta, steps, reading


def test_reading_repeats(recorded):
    meta, _, reading = recorded
    want = meta["reading"]
    assert reading.window_s == want["window_s"]
    assert reading.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert [list(x) for x in reading.device_ops] == want["device_ops"]
    assert [list(x) for x in reading.idle_gaps] == want["idle_gaps"]
    # the chip run's own reading: the same window, busy time within the
    # clock offset the reduction now corrects for
    assert reading.window_s == meta["chip_reading"]["window_s"]
    assert reading.busy_s == pytest.approx(meta["chip_reading"]["busy_s"],
                                           rel=0.05)


def test_busy_and_idle_add_up(recorded):
    _, _, reading = recorded
    assert 0 < reading.busy_s < reading.window_s
    idle = sum(s for _, s in reading.idle_gaps)
    assert idle == pytest.approx(reading.window_s - reading.busy_s, rel=1e-6)
    assert {n for n, _ in reading.idle_gaps} <= LABELS
    assert reading.device_ops and all(s > 0 for _, s in reading.device_ops)


def test_every_dispatch_has_its_device_run(recorded):
    _, steps, reading = recorded
    assert [s for s, _ in reading.timed
            if s.kind == "prefill"] == [s for s in steps if s.kind == "prefill"]
    assert len(reading.timed) == len(steps)
    acc = reading.step_times("decode", "accurate")
    fast = reading.step_times("decode", "fast")
    assert len(acc) == sum(s.kind == "decode" and s.rung == "accurate"
                           for s in steps)
    assert len(fast) == sum(s.kind == "decode" and s.rung == "fast"
                            for s in steps)
    assert all(t > 0 for t in acc + fast)
    assert sum(s.catchup for s in steps) > 0


def test_count_mismatch_reads_nothing(recorded):
    meta, steps, _ = recorded
    short = trace.read(DATA / "tiny.xplane.pb", meta["modules"], steps[:-1],
                       n_devices=1)
    assert short.timed == []
    assert short.busy_s == pytest.approx(recorded[2].busy_s, rel=0.05)
