"""The serving launcher's two-rung plane (``repro.launch.serve``).

A reduced internlm2 plane at float32 decodes under the queue-pressure
schedule (accurate -> fast -> accurate).  Each rung's decode logits,
including the accurate rung's after the switch back, which first replays
the tokens the fast rung served, are held to that rung's own
teacher-forced ``forward`` over the same tokens.  The call's spans and
counters describe what it did, and reach a profiler trace as host events.
A reduced plane at bfloat16 hands its logits over widened, exactly, to
float32, each step's in its own row.
"""

import dataclasses
import importlib.util
import sys
from collections import Counter
from pathlib import Path

import jax
import ml_dtypes
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
    # the hand-off drains between served steps, and last inside the wait
    *between, final = spans_named(res, "serve.handoff")
    assert res.spans[final.parent] is d_wait
    assert d_wait.start_ns <= final.start_ns <= final.end_ns <= d_wait.end_ns
    assert all(res.spans[h.parent].name == "serve.call"
               and decode[0].end_ns <= h.start_ns for h in between)
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
    assert not spans_named(res, "serve.handoff.stack")
    widen = spans_named(res, "serve.handoff.widen")
    assert len(widen) == 1 + TOKENS          # one per logit array
    assert {res.spans[s.parent].name for s in transfers + widen} == {
        "serve.handoff"}


def test_done_steps_drain_between_dispatches(served, monkeypatch):
    """With every step done by the time its dispatch returns, each served
    step is handed over right after its own dispatch, and the wait holds
    only the prefill's logits.  The result is the same, bit for bit."""
    res, _, plane, prompt = served
    for rung, fn in list(plane.step_fns.items()):
        monkeypatch.setitem(plane.step_fns, rung,
                            lambda *a, fn=fn: jax.block_until_ready(fn(*a)))
    synced = plane.generate(prompt, TOKENS)
    *between, final = spans_named(synced, "serve.handoff")
    assert len(between) == TOKENS
    inside = Counter(s.name for s in synced.spans
                     if s.parent >= 0 and synced.spans[s.parent] is final)
    assert inside == {"serve.handoff.transfer": 1, "serve.handoff.widen": 1}
    assert synced.counters["host_bytes"] == res.counters["host_bytes"]
    for got, want in ((synced.logits, res.logits),
                      (synced.prefill_logits, res.prefill_logits),
                      (synced.inputs, res.inputs)):
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))


def test_handoff_overlap_is_counted(served):
    """At most every decode step's logits are widened while the last
    step still runs; none on a device that finishes first."""
    res = served[0]
    assert 0 <= res.counters["handoff_overlapped"] <= TOKENS


def test_call_line_reads_the_spans(served):
    res = served[0]
    line = serve.call_line(res)
    assert line.startswith(f"call {res.spans[0].call}: prefill "
                           f"{res.prefill_s * 1e3:.1f} ms, decode "
                           f"{res.decode_s * 1e3:.1f} ms, hand-off ")
    assert f"{res.counters['host_bytes'] / 1e6:.2f} MB to the host" in line
    assert "widening" in line
    assert (f"{res.counters['handoff_overlapped']} of {TOKENS} steps "
            "while decode ran") in line


def test_another_length_compiles_nothing(served):
    """The hand-off is host work: a call of another length runs only the
    programs the first call built."""
    _, _, plane, prompt = served
    compiled = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        res = plane.generate(prompt, TOKENS - 5)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert res.logits.shape == (TOKENS - 5, BATCH, 512)
    assert compiled == []


BF16_SPECIALS = [0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1.0, -2.5,
                 3.0e38, -1.0e-38, 1.0e-40, -9.2e-41, 65504.0, 1.0e-45]


@pytest.mark.parametrize("values", ["specials", "all_bits", "float32"])
def test_widen_is_astype_bit_for_bit(values):
    """Widening a bfloat16 array matches ml_dtypes' ``astype(float32)``
    to the bit: signed zeros, infinities, NaNs, subnormals and ordinary
    values alike.  A float32 array is copied as it is."""
    bf16 = ml_dtypes.bfloat16
    if values == "specials":
        x = np.array(BF16_SPECIALS, np.float32).astype(bf16).reshape(2, 7)
        assert (np.abs(x.astype(np.float32)) < 1.18e-38).any()   # subnormal
    elif values == "all_bits":
        x = np.arange(2**16, dtype=np.uint16).view(bf16).reshape(256, 256)
    else:
        x = np.array(BF16_SPECIALS, np.float32).reshape(2, 7)
    out = np.full(x.shape, 7.0, np.float32)
    serve.widen(x, out)
    np.testing.assert_array_equal(out.view(np.uint32),
                                  x.astype(np.float32).view(np.uint32))


@pytest.fixture(scope="module")
def served_bf16():
    cfg = dataclasses.replace(reduced_config("internlm2-1.8b"),
                              dtype="bfloat16")
    plane = serve.ServingPlane(
        cfg, mesh=serve.build_mesh(devices=jax.devices()[:1]),
        window=WINDOW, batch=BATCH, prompt_len=PROMPT, max_new=TOKENS)
    return plane.generate(plane.prompt(BATCH, PROMPT), TOKENS)


def test_bf16_logits_arrive_as_exact_float32(served_bf16):
    """The plane's logits leave the device at bfloat16 and reach the
    result as float32 that round-trip through bfloat16 unchanged."""
    res = served_bf16
    assert res.logits.dtype == res.prefill_logits.dtype == np.float32
    assert res.logits.shape == (TOKENS, BATCH, 512)
    assert res.prefill_logits.shape == (BATCH, 512)
    assert res.counters["host_bytes"] == (2 * BATCH * 512 * (TOKENS + 1)
                                          + 4 * BATCH * TOKENS)
    for a in (res.logits, res.prefill_logits):
        assert np.isfinite(a).all()
        back = a.astype(ml_dtypes.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(back.view(np.uint32), a.view(np.uint32))


def test_bf16_step_logits_land_in_their_row(served_bf16):
    """Step i's greedy pick, made on the device from its bfloat16 logits,
    is the token fed to step i + 1: the host's argmax over row i of the
    widened logits finds it, so row i holds step i's logits."""
    res = served_bf16
    np.testing.assert_array_equal(res.tokens[:-1], res.inputs[1:])
    np.testing.assert_array_equal(res.prefill_logits.argmax(-1),
                                  res.inputs[0])


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
    assert host["serve.handoff"] == len(spans_named(res, "serve.handoff"))
    assert host["serve.decode"] == 3
    assert host["serve.catchup"] == res.catch_up_steps
    assert np.isfinite(res.logits).all()
