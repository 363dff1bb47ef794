"""The plain reference agrees with the serving program where both compute
in float32: the accurate rung with full attention, the fast rung with its
window and its int8 cache.  This is what lets the check read a bf16
program's departures as rounding, and nothing else."""

import dataclasses

import numpy as np
import pytest

from chipbench import check, families, harness, weights
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def served():
    import jax

    from repro.launch import serve

    config = dict(tiny.CONFIG)
    config["serving"] = dict(config["serving"], dtype="float32")
    arch = families.arch(config)
    cfg = arch.program_config(config)
    t = tiny.TRAFFIC
    plane = serve.ServingPlane(
        cfg, mesh=serve.build_mesh(devices=jax.devices()[:1]),
        window=config["serving"]["fast"]["sliding_window"], batch=t["batch"],
        prompt_len=t["prompt_len"], max_new=t["new_tokens"])
    weights.install(plane, arch, 5)
    prompt = harness.prompts(5, 1, t["batch"], t["prompt_len"], arch.vocab)[0]
    with jax.default_matmul_precision("highest"):
        res = plane.generate(jax.device_put(prompt, plane.tokens_sh),
                             t["new_tokens"])
    return config, arch, prompt, res


def test_reference_matches_float32_program(served):
    config, arch, prompt, res = served
    pos_rungs = check.position_rungs(res.rungs)
    assert set(pos_rungs) == {"accurate", "fast"}
    w = weights.make(arch, 5)
    ref = check.reference_logits(arch, config["serving"], w, prompt,
                                 res.inputs.T, set(pos_rungs))
    got = np.concatenate([res.prefill_logits[None], res.logits])  # (n+1,B,V)
    for j, rung in enumerate(pos_rungs):
        diff = np.abs(ref[rung][:, j] - got[j]).max()
        assert diff < 2e-3, (j, rung, diff)


def test_fast_rung_is_not_full_attention(served):
    """The fast rung's steps after the window fills differ from full
    attention: the windowed reference is not a copy of the other."""
    config, arch, prompt, res = served
    w = weights.make(arch, 5)
    both = check.reference_logits(arch, config["serving"], w, prompt,
                                  res.inputs.T, {"accurate", "fast"})
    assert np.abs(both["accurate"][:, -1] - both["fast"][:, -1]).max() > 0.05
