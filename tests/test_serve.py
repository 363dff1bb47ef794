"""The serving launcher's two-rung plane (``repro.launch.serve``).

A reduced internlm2 plane at float32 decodes under the queue-pressure
schedule (accurate -> fast -> accurate).  Each rung's decode logits,
including the accurate rung's after the switch back, which first replays
the tokens the fast rung served, are held to that rung's own
teacher-forced ``forward`` over the same tokens.
"""

import dataclasses

import jax
import pytest

import repro.configs  # noqa: F401
from repro.configs.reduced import reduced_config
from repro.launch import serve

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
    return res, agree


def test_schedule_switches_both_ways(served):
    res, _ = served
    third = TOKENS // 3
    assert res.rungs == (["accurate"] * third + ["fast"] * third
                         + ["accurate"] * (TOKENS - 2 * third))
    assert [(i, a, b) for i, a, b, _ in res.switches] == [
        (third, "accurate", "fast"), (2 * third, "fast", "accurate")]
    assert res.logits.shape == (TOKENS, BATCH, 512)
    assert res.inputs.shape == (TOKENS, BATCH)


def test_catch_up_replays_every_missed_token(served):
    """Each rung replays exactly the decode inputs the other one served."""
    res, _ = served
    assert res.catch_up_steps == 2 * (TOKENS // 3)


def test_accurate_rung_matches_forward_after_switch_back(served):
    """Prefill, the steps before the switch and the steps after the switch
    back all sit within float32 rounding of the full-attention forward:
    the caches and positions the replay rebuilt are the right ones."""
    _, agree = served
    max_rel, min_corr, _ = agree["accurate"]["accurate"]
    assert max_rel <= 1e-3
    assert min_corr >= 0.9999


def test_fast_rung_matches_its_window_forward(served):
    """The fast rung (sliding window, int8 KV) is held to the
    sliding-window forward with full-precision KV.  int8 KV moves this
    random-init model's logits (``tests/test_kv_int8.py``), so the bound
    is on the mean correlation, and the rung must sit closer to its own
    window than to full attention."""
    _, agree = served
    own = agree["fast"]["fast"]
    full = agree["fast"]["accurate"]
    assert own[2] >= 0.9
    assert own[2] > full[2]
