"""The serving launcher's two-rung plane (``repro.launch.serve``).

A reduced internlm2 plane at float32 decodes under the queue-pressure
schedule (accurate -> fast -> accurate).  Each rung's decode logits,
including the accurate rung's after the switch back, which first replays
the tokens the fast rung served, are held to that rung's own
teacher-forced ``forward`` over the same tokens.  The call's spans and
counters describe what it did, and reach a profiler trace as host events.
"""

import dataclasses
import importlib.util
import sys
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.configs  # noqa: F401
from repro.configs.reduced import reduced_config
from repro.launch import serve

CHIPBENCH_TRACE = Path(__file__).resolve().parents[1] / "chipbench" / "trace.py"
BATCH, PROMPT, WINDOW, TOKENS = 4, 32, 16, 12


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(reduced_config("internlm2-1.8b"),
                              dtype="float32")
    plane = serve.ServingPlane(
        cfg, mesh=serve.build_mesh(devices=jax.devices()[:1]),
        window=WINDOW, batch=BATCH, prompt_len=PROMPT, max_new=TOKENS)
    prompt = plane.prompt(BATCH, PROMPT)
    res = plane.generate(prompt, TOKENS)
    agree = serve.rung_agreement(serve.reference_forwards(cfg, WINDOW),
                                 plane.params, prompt, res, rows=BATCH)
    return res, agree, plane, prompt


def test_schedule_switches_both_ways(served):
    res = served[0]
    third = TOKENS // 3
    assert res.rungs == (["accurate"] * third + ["fast"] * third
                         + ["accurate"] * (TOKENS - 2 * third))
    assert [(i, a, b) for i, a, b, _ in res.switches] == [
        (third, "accurate", "fast"), (2 * third, "fast", "accurate")]
    assert res.logits.shape == (TOKENS, BATCH, 512)
    assert res.inputs.shape == (TOKENS, BATCH)


def test_catch_up_replays_every_missed_token(served):
    """Each rung replays exactly the decode inputs the other one served."""
    res = served[0]
    assert res.catch_up_steps == 2 * (TOKENS // 3)


def test_accurate_rung_matches_forward_after_switch_back(served):
    """Prefill, the steps before the switch and the steps after the switch
    back all sit within float32 rounding of the full-attention forward:
    the caches and positions the replay rebuilt are the right ones."""
    agree = served[1]
    max_rel, min_corr, _ = agree["accurate"]["accurate"]
    assert max_rel <= 1e-3
    assert min_corr >= 0.9999


def test_fast_rung_matches_its_window_forward(served):
    """The fast rung (sliding window, int8 KV) is held to the
    sliding-window forward with full-precision KV.  int8 KV moves this
    random-init model's logits (``tests/test_kv_int8.py``), so the bound
    is on the mean correlation, and the rung must sit closer to its own
    window than to full attention."""
    agree = served[1]
    own = agree["fast"]["fast"]
    full = agree["fast"]["accurate"]
    assert own[2] >= 0.9
    assert own[2] > full[2]


def spans_named(res, name):
    return [s for s in res.spans if s.name == name]


def test_one_call_root_shares_its_id(served):
    res = served[0]
    roots = [s for s in res.spans if s.parent == -1]
    assert [s.name for s in roots] == ["serve.call"]
    assert roots[0].attrs["call"] == roots[0].call
    assert {s.call for s in res.spans} == {roots[0].call}
    for s in res.spans[1:]:
        outer = res.spans[s.parent]
        assert outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns


def test_calls_have_their_own_ids(served):
    _, _, plane, prompt = served
    a = plane.generate(prompt, 3)
    b = plane.generate(prompt, 3)
    assert b.spans[0].call == a.spans[0].call + 1


def test_prefill_and_decode_spans_follow_the_schedule(served):
    res = served[0]
    assert [s.attrs["rung"] for s in spans_named(res, "serve.prefill")] \
        == list(serve.RUNGS)
    decode = spans_named(res, "serve.decode")
    assert [s.attrs["rung"] for s in decode] == res.rungs
    assert [s.attrs["step"] for s in decode] == list(range(TOKENS))
    assert [s.attrs["pos"] for s in decode] == [PROMPT + i
                                                for i in range(TOKENS)]


def test_catchup_spans_sit_under_their_switch(served):
    res = served[0]
    catchup = spans_named(res, "serve.catchup")
    assert len(catchup) == 2 * (TOKENS // 3) == res.catch_up_steps
    assert res.counters["catch_up_steps"] == len(catchup)
    replayed = Counter()
    for s in catchup:
        switch = res.spans[s.parent]
        assert switch.name == "serve.switch"
        assert s.attrs["rung"] == switch.attrs["to"]
        replayed[s.parent] += 1
    for i, n in replayed.items():
        assert res.spans[i].attrs["replayed"] == n
    # each rung replays the positions it missed, in order
    third = TOKENS // 3
    assert [s.attrs["pos"] for s in catchup] == (
        [PROMPT + k for k in range(third)]
        + [PROMPT + k for k in range(third, 2 * third)])


def test_switches_are_read_from_the_switch_spans(served):
    res = served[0]
    assert res.switches == [
        (s.attrs["step"], s.attrs["frm"], s.attrs["to"], s.attrs["depth"])
        for s in spans_named(res, "serve.switch")]
    assert [d for *_, d in res.switches] == [10, 0]


def test_prefill_and_decode_times_are_their_spans(served):
    res = served[0]
    prefill = spans_named(res, "serve.prefill")
    (p_wait,) = spans_named(res, "serve.prefill.wait")
    assert res.prefill_s == (p_wait.end_ns - prefill[0].start_ns) * 1e-9
    assert prefill[-1].end_ns <= p_wait.start_ns
    decode = spans_named(res, "serve.decode")
    (d_wait,) = spans_named(res, "serve.decode.wait")
    assert res.decode_s == (d_wait.end_ns - decode[0].start_ns) * 1e-9
    assert p_wait.end_ns <= decode[0].start_ns
    assert all(decode[0].start_ns <= s.start_ns and s.end_ns <= d_wait.start_ns
               for s in res.spans
               if s.name in ("serve.decode", "serve.switch", "serve.catchup"))
    (handoff,) = spans_named(res, "serve.handoff")
    assert handoff.start_ns >= d_wait.end_ns
    assert 0 < res.prefill_s and 0 < res.decode_s


def test_handoff_counts_the_bytes_it_copies(served):
    """The plane is float32, so what leaves the device is what the result
    holds: the prefill's logits, one token and one logit row per step."""
    res = served[0]
    want = res.prefill_logits.nbytes + res.logits.nbytes + res.inputs.nbytes
    assert want == 4 * BATCH * (512 * (TOKENS + 1) + TOKENS)
    assert res.counters["host_bytes"] == want
    (handoff,) = spans_named(res, "serve.handoff")
    transfers = spans_named(res, "serve.handoff.transfer")
    assert len(transfers) == 1 + 2 * TOKENS
    assert sum(s.attrs["bytes"] for s in transfers) == want
    (stack,) = spans_named(res, "serve.handoff.stack")
    assert {res.spans[s.parent].name for s in transfers + [stack]} == {
        "serve.handoff"}


def test_call_line_reads_the_spans(served):
    res = served[0]
    line = serve.call_line(res)
    assert line.startswith(f"call {res.spans[0].call}: prefill "
                           f"{res.prefill_s * 1e3:.1f} ms, decode "
                           f"{res.decode_s * 1e3:.1f} ms, hand-off ")
    assert f"{res.counters['host_bytes'] / 1e6:.2f} MB to the host" in line


def benchmark_annotations():
    """``ANNOTATIONS`` of the benchmark's trace reduction, loaded from its
    file."""
    spec = importlib.util.spec_from_file_location("chipbench_trace",
                                                  CHIPBENCH_TRACE)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    try:
        spec.loader.exec_module(module)
    finally:
        del sys.modules[spec.name]
    return set(module.ANNOTATIONS)


def test_span_names_are_not_the_benchmarks_annotations(served):
    """A program span named like one of the benchmark's dispatch
    annotations would be matched to a device program run."""
    names = {s.name for s in served[0].spans}
    annotations = benchmark_annotations()
    assert {"call", "prefill", "catchup"} <= annotations
    assert all(n.startswith("serve.") for n in names)
    assert not names & annotations


def test_spans_reach_the_profiler_trace(served, tmp_path):
    _, _, plane, prompt = served
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = plane.generate(prompt, 3)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    host = Counter(e.name for plane_ in data.planes
                   if plane_.name.startswith("/host:")
                   for line in plane_.lines for e in line.events)
    assert host["serve.handoff"] == 1
    assert host["serve.decode"] == 3
    assert host["serve.catchup"] == res.catch_up_steps
    assert np.isfinite(res.logits).all()
