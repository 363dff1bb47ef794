"""One expert-parallel rank's share of a fine-grained MoE layer, served.

At a reduced size on the CPU (a dense first layer and 2 MoE layers, d 64,
16 routed experts of which 4 are held, top-4 with unscaled probabilities,
1 shared expert; ``chipbench/tests/tiny_moe.py``), on seeded weights, the
serving plane is held to the plain float32 reference of
``chipbench/families/moe.py``: through the cache on both rungs, layer by
layer for the dispatch, and in the count of assignments its held experts
served.  The share test ties the cut to the model: the four shares'
outputs, with the shared expert counted once, add up to the uncut layer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, families, harness, weights
from chipbench.families import moe as ref_moe
from chipbench.tests import tiny_moe
from repro.launch import serve
from repro.models import moe
from repro.models.layers import mlp_apply

SEED = 7


def float32_config():
    config = dict(tiny_moe.CONFIG)
    config["serving"] = dict(config["serving"], dtype="float32")
    return config


@pytest.fixture(scope="module")
def served():
    config = float32_config()
    arch = families.arch(config)
    t = tiny_moe.TRAFFIC
    plane = serve.ServingPlane(
        arch.program_config(config),
        mesh=serve.build_mesh(devices=jax.devices()[:1]),
        window=config["serving"]["fast"]["sliding_window"], batch=t["batch"],
        prompt_len=t["prompt_len"], max_new=t["new_tokens"])
    weights.install(plane, arch, SEED)
    prompt = harness.prompts(SEED, 1, t["batch"], t["prompt_len"],
                             arch.vocab)[0]
    with jax.default_matmul_precision("highest"):
        res = plane.generate(jax.device_put(prompt, plane.tokens_sh),
                             t["new_tokens"])
    return config, arch, prompt, res


def test_prefill_then_decode_matches_reference_on_both_rungs(served):
    """Every served position (the accurate prefill, each rung's decode
    steps through its cache) against the reference's full forward of the
    same tokens.  Both sides compute in float32 at the highest matmul
    precision, so what is left is float32 rounding over three layers:
    the logits agree to 2e-3, which an expert dropped or misrouted (a
    change of order 0.1) or a bf16 step (order 1e-2) would not meet."""
    config, arch, prompt, res = served
    pos_rungs = check.position_rungs(res.rungs)
    assert set(pos_rungs) == {"accurate", "fast"}
    w = weights.make(arch, SEED)
    ref = check.reference_logits(arch, config["serving"], w, prompt,
                                 res.inputs.T, set(pos_rungs))
    got = np.concatenate([res.prefill_logits[None], res.logits])
    for j, rung in enumerate(pos_rungs):
        diff = np.abs(ref[rung][:, j] - got[j]).max()
        assert diff < 2e-3, (j, rung, diff)


def test_held_assignments_counter_is_the_reference_routing(served):
    """``moe_held_assignments`` equals the assignments to experts 4-7 that
    the reference's router makes at every position each rung decoded
    (catch-up steps included), summed over the MoE layers."""
    config, arch, prompt, res = served
    w = weights.make(arch, SEED)
    seq = jnp.asarray(np.concatenate([prompt, res.inputs.T], axis=1))
    p = prompt.shape[1]
    want = 0
    for rung in serve.RUNGS:
        last = max(i for i, r in enumerate(res.rungs) if r == rung)
        kw = dict(eps=arch.norm_eps, theta=arch.rope_theta, operand=None,
                  **check.rung_reference(config["serving"], rung, p))
        with jax.default_matmul_precision("highest"):
            x = w["embed"]["embedding"][seq]
            x = ref_moe.dense_layer(x, w["first_dense"][0], 0, **kw)
            seg = w["decoder"][0]
            for i in range(arch.moe_layers):
                lw = jax.tree.map(lambda a: a[0, i], seg)
                x = ref_moe.attend(x, lw, **kw)
                h = ref_moe.ref.rmsnorm(x, lw["mlp_norm"], arch.norm_eps)
                _, idx = ref_moe.routing(h, lw["moe"]["router"],
                                         top_k=arch.top_k, norm_topk=False,
                                         operand=None)
                mine = (idx >= 4) & (idx < 8)
                want += int(mine[:, p:p + last + 1].sum())
                x = x + ref_moe.moe_ffn(h, lw["moe"], top_k=arch.top_k,
                                        first=4, norm_topk=False,
                                        operand=None)
    assert res.counters["moe_held_assignments"] == want
    assert 0 < want < 2 * 64 * 4 * 12 * 4


def test_call_line_reports_held_assignments(served):
    *_, res = served
    n = res.counters["moe_held_assignments"]
    assert serve.call_line(res).endswith(f"; moe_held_assignments {n}")
    load = [s for s in res.spans if s.name == "serve.moe.load"]
    assert len(load) == 1 and load[0].attrs["bytes"] == 2 * 2 * 4
    wait = next(s for s in res.spans if s.name == "serve.decode.wait")
    assert load[0].start_ns >= wait.end_ns


def layer_inputs(seed=0, tokens=48, d=64, experts=16, skew=False):
    """Seeded weights of one MoE layer (all 16 experts), and its input.
    With ``skew`` the router's columns share a direction of the input, so
    that expert 4 is chosen by (nearly) every token and expert 5 by
    none."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    f = 32
    x = jax.random.normal(ks[0], (tokens, d))
    router = jax.random.normal(ks[1], (d, experts)) / np.sqrt(d)
    if skew:
        u = jnp.ones((d,)) / np.sqrt(d)
        x = x + 3.0 * u
        router = router.at[:, 4].add(2.0 * u).at[:, 5].add(-2.0 * u)
    mw = {
        "router": router,
        "experts": {
            "wi": jax.random.normal(ks[2], (experts, d, f)) / np.sqrt(d),
            "wg": jax.random.normal(ks[3], (experts, d, f)) / np.sqrt(d),
            "wo": jax.random.normal(ks[4], (experts, f, d)) / np.sqrt(f),
        },
        "shared": {
            "wi": jax.random.normal(ks[5], (d, f)) / np.sqrt(d),
            "wg": jax.random.normal(ks[6], (d, f)) / np.sqrt(d),
            "wo": jax.random.normal(ks[7], (f, d)) / np.sqrt(f),
        },
    }
    return mw, x


def share(mw, first, held):
    return dict(mw, experts=jax.tree.map(lambda a: a[first:first + held],
                                         mw["experts"]))


def layer_config(first=4, held=4, **kw):
    cfg = families.arch(tiny_moe.CONFIG).program_config(float32_config())
    return dataclasses.replace(cfg, expert_first=first, experts_held=held,
                               **kw)


def reference_ffn(mw, x, first, norm_topk=False):
    with jax.default_matmul_precision("highest"):
        return ref_moe.moe_ffn(x[None], mw, top_k=4, first=first,
                               norm_topk=norm_topk, operand=None)[0]


def served_ffn(mw, x, cfg):
    with jax.default_matmul_precision("highest"):
        y, held = moe.moe_serve(mw, x[None], cfg)
    return y[0], int(held)


def test_shares_add_up_to_the_uncut_layer():
    """Four ranks of 4 experts each: their outputs, less the shared
    expert that each of them computes (counted once), sum to the uncut
    reference layer (all 16 held).  Float32 on both sides: the sum of
    five terms of order 1 agrees to 1e-5."""
    mw, x = layer_inputs()
    with jax.default_matmul_precision("highest"):
        shared = mlp_apply(mw["shared"], x, "swiglu")
    parts, counts = zip(*(served_ffn(share(mw, r * 4, 4), x,
                                     layer_config(first=r * 4))
                          for r in range(4)))
    whole = reference_ffn(mw, x, first=0)
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, atol=1e-5)
    assert sum(counts) == 48 * 4          # every assignment held by one rank


@pytest.mark.parametrize("skew", [False, True], ids=["uniform", "skewed"])
@pytest.mark.parametrize("impl", ["grouped", "dense", "gshard"])
def test_dispatch_matches_reference_layer(skew, impl):
    """Rank 1's share (experts 4-7) by each dispatch against the
    reference's MoE layer, to float32 rounding (1e-5 on outputs of order
    1).  Skewed, expert 4 gets most of the tokens and expert 5 none: the
    grouped dispatch has an empty group and a full one, and drops
    nothing; gshard runs with capacity for every assignment."""
    mw, x = layer_inputs(skew=skew)
    cfg = layer_config(capacity_factor=16.0, moe_impl=impl)
    mine = share(mw, 4, 4)
    want = reference_ffn(mine, x, first=4)
    if impl == "grouped":
        got, held = served_ffn(mine, x, cfg)
    else:
        with jax.default_matmul_precision("highest"):
            got = moe.moe_apply(mine, x[None], cfg)[0][0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    _, idx, _ = moe.route(mw["router"], x, 4, normalize=False)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=16)
    if skew:
        assert counts[5] == 0 and counts[4] >= 0.9 * 48
    if impl == "grouped":
        assert held == int(counts[4:8].sum())


def test_unscaled_top_k_probabilities():
    """``norm_topk_prob`` false: the top-k probabilities are the softmax's
    own (summing below 1); true: rescaled to sum 1."""
    mw, x = layer_inputs()
    probs = jax.nn.softmax(x @ mw["router"], axis=-1)
    raw, idx, _ = moe.route(mw["router"], x, 4, normalize=False)
    np.testing.assert_allclose(
        raw, np.take_along_axis(np.asarray(probs), np.asarray(idx), -1),
        rtol=1e-6)
    assert (np.asarray(raw.sum(-1)) < 1 - 1e-3).all()
    scaled, _, _ = moe.route(mw["router"], x, 4, normalize=True)
    np.testing.assert_allclose(scaled.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(scaled * raw.sum(-1, keepdims=True), raw,
                               rtol=1e-5)
    # the served layer follows the config's choice
    mine = share(mw, 4, 4)
    a = served_ffn(mine, x, layer_config())[0]
    b = served_ffn(mine, x, layer_config(norm_topk_prob=True))[0]
    np.testing.assert_allclose(a, reference_ffn(mine, x, 4), atol=1e-5)
    np.testing.assert_allclose(b, reference_ffn(mine, x, 4, norm_topk=True),
                               atol=1e-5)
    assert np.abs(np.asarray(a - b)).max() > 1e-2


def test_share_outside_the_routed_experts_is_refused():
    with pytest.raises(ValueError):
        layer_config(first=14, held=4)
