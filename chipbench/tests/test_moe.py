"""The MoE family (``families/moe.py``): its costs counted by hand, its
reference held to the float32 program, and its tiny cell run end to end
through the harness on the CPU."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import check, families, harness, weights
from chipbench.families import dense, moe
from chipbench.tests import tiny_moe

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def deepseek():
    return families.arch(json.loads(
        (CONFIGS / "deepseek-moe-16b.json").read_text()))


def test_deepseek_share_parameter_counts():
    # dense layer: attention 4*2048*2048 + SwiGLU 3*2048*10944; each MoE
    # layer: attention, router 2048*64, 8 held experts 8*3*2048*1408 and 2
    # shared 3*2048*2816; embedding and head 2*2048*102400; 29 norms
    a = deepseek()
    assert (a.layers, a.moe_layers, a.experts, a.held) == (14, 13, 64, 8)
    assert a.dense_layer_params == 84_017_152
    assert a.moe_layer_dense_params + 8 * a.expert_params == 103_415_808
    assert 8 * a.expert_params == 69_206_016
    assert 2 * a.expert_params == 17_301_504
    assert a.params == (84_017_152 + 13 * 103_415_808 + 419_430_400
                        + 29 * 2048) == 1_847_912_448


def test_routed_flops_are_the_held_share():
    """A step's routed work is tokens x k x 8/64 expert passes, whatever
    the dispatch computes."""
    a = deepseek()
    assert a.routed_passes(64) == 64 * 6 * 8 / 64 == 48
    base = a.decode_cost(batch=64, pos=130, window=0, kv_dtype="bfloat16")
    cut = dataclasses.replace(a, held=4).decode_cost(
        batch=64, pos=130, window=0, kv_dtype="bfloat16")
    assert base.flops - cut.flops == 2 * a.expert_params * 13 * 64 * 6 * 4 / 64
    p = a.prefill_cost(batch=64, prompt_len=128, window=0,
                       kv_dtype="bfloat16")
    routed = 2 * a.expert_params * 13 * a.routed_passes(64 * 128)
    assert routed / p.flops == pytest.approx(0.137, abs=0.005)


def test_expected_touched_experts():
    a = deepseek()
    assert a.touched(1) == pytest.approx(8 * 6 / 64)
    assert a.touched(64) == pytest.approx(8 * (1 - (58 / 64) ** 64))
    assert 7.98 < a.touched(64) < 8.0
    one = a.decode_cost(batch=1, pos=0, window=0, kv_dtype="bfloat16")
    many = a.decode_cost(batch=64, pos=0, window=0, kv_dtype="bfloat16")
    experts = 2 * 13 * a.expert_params * (a.touched(64) - a.touched(1))
    rest = 63 * (2 * 2048                                    # embedding rows
                 + 14 * 16 * 2 * 128 * 2 * 2                 # kv read, written
                 + 102400 * 2 + 2048 * 2 * 14 * 4)           # logits, acts
    assert many.bytes - one.bytes == pytest.approx(experts + rest)


def test_one_expert_of_one_is_the_dense_layer_plus_its_router():
    """With one routed expert of one, chosen by every token, and no shared
    or leading dense layer, a MoE step is a dense step plus the router's
    (d x 1) matmul."""
    d = dense.Arch(layers=3, d=256, heads=4, kv_heads=2, head_dim=64,
                   ffn=512, vocab=512, rope_theta=1e4, norm_eps=1e-5)
    m = moe.Arch(layers=3, dense_layers=0, d=256, heads=4, kv_heads=2,
                 head_dim=64, dense_ffn=0, expert_ffn=512, experts=1,
                 held=1, first_held=0, shared=0, top_k=1, norm_topk=True,
                 vocab=512, rope_theta=1e4, norm_eps=1e-5)
    for kw in (dict(batch=8, pos=40, window=0, kv_dtype="bfloat16"),
               dict(batch=2, pos=300, window=256, kv_dtype="int8")):
        dc, mc = d.decode_cost(**kw), m.decode_cost(**kw)
        assert mc.flops - dc.flops == kw["batch"] * 2 * 3 * 256
        assert mc.bytes - dc.bytes == 2 * 3 * 256
    dp = d.prefill_cost(batch=2, prompt_len=16, window=0, kv_dtype="bfloat16")
    mp = m.prefill_cost(batch=2, prompt_len=16, window=0, kv_dtype="bfloat16")
    assert mp.flops - dp.flops == 2 * 16 * 2 * 3 * 256
    assert mp.bytes - dp.bytes == 2 * 3 * 256
    assert m.params - d.params == 3 * 256


def test_program_refuses_routing_it_does_not_compute():
    config = dict(tiny_moe.CONFIG, scoring_func="sigmoid")
    with pytest.raises(ValueError):
        moe.Arch.program_config(config)


@pytest.fixture(scope="module")
def served():
    import jax

    from repro.launch import serve

    config = dict(tiny_moe.CONFIG)
    config["serving"] = dict(config["serving"], dtype="float32")
    arch = families.arch(config)
    t = tiny_moe.TRAFFIC
    plane = serve.ServingPlane(
        arch.program_config(config),
        mesh=serve.build_mesh(devices=jax.devices()[:1]),
        window=config["serving"]["fast"]["sliding_window"], batch=t["batch"],
        prompt_len=t["prompt_len"], max_new=t["new_tokens"])
    weights.install(plane, arch, 5)
    prompt = harness.prompts(5, 1, t["batch"], t["prompt_len"], arch.vocab)[0]
    with jax.default_matmul_precision("highest"):
        res = plane.generate(jax.device_put(prompt, plane.tokens_sh),
                             t["new_tokens"])
    return config, arch, prompt, res


def test_reference_matches_float32_program(served):
    """Prefill and every decode step of both rungs, through the cache,
    against the reference's full forward: float32 on both sides, so the
    bound (as in ``test_reference.py``) is rounding of the order of the
    logits' 1e-6 relative error summed over three layers."""
    config, arch, prompt, res = served
    pos_rungs = check.position_rungs(res.rungs)
    w = weights.make(arch, 5)
    ref = check.reference_logits(arch, config["serving"], w, prompt,
                                 res.inputs.T, set(pos_rungs))
    got = np.concatenate([res.prefill_logits[None], res.logits])
    for j, rung in enumerate(pos_rungs):
        diff = np.abs(ref[rung][:, j] - got[j]).max()
        assert diff < 2e-3, (j, rung, diff)
    assert res.counters["moe_held_assignments"] > 0


def test_sound_tiny_moe_run_is_correct(tmp_path):
    import jax

    result, compared = harness.run_cell(
        tiny_moe.cell(), seed=2**31 + 91, seconds=0.6, trace=False,
        devices=jax.devices()[:1], t_process=0.0, root=tmp_path)
    assert result["correct"], compared
    assert result["failed"] == 0
    assert compared["off_schedule"]["value"] == 0


def test_control_fails_where_program_passes():
    """The fp8 control fails the tiny MoE cell's limits on a seed on which
    the bf16 program passes them (the readings are in ``tiny_moe.py``)."""
    import jax

    cell = tiny_moe.cell()
    seed = 14
    plane, arch = harness.build_plane(cell, jax.devices()[:1], seed)
    t = cell.traffic
    host = harness.prompts(seed, 2, t["batch"], t["prompt_len"], arch.vocab)
    got = harness.collect(
        [harness.answer(plane.generate(jax.device_put(x, plane.tokens_sh),
                                       t["new_tokens"])) for x in host],
        host, t)
    r = harness.compare(cell, arch, seed, got, control=True)
    limits = {k: v["limit"] for k, v in cell.limits.items()}
    assert all(r[k] <= limits[k] for k in limits), r
    assert any(r[f"control.{k}"] > limits[k] for k in limits), r
