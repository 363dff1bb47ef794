"""The serving plane holds its parameters at the dtype its programs compute
in (``serve.held_dtype``, set on both rungs by ``serve.rung_configs``).

Each program used to round every float32 parameter to bfloat16 at its top;
it now gets the bfloat16 values themselves.  At the tiny dense and MoE
cells' widths (``chipbench/tests/tiny.py``, ``tiny_moe.py``: ``dtype``
bfloat16, ``param_dtype`` float32), each rung's prefill and decode steps
give the same bits on float32 parameters and on the same parameters cast
to bfloat16 first, and with bfloat16 parameters nothing casts them.  A
bfloat16 plane holds, compiles for and installs bfloat16 parameters,
equal to the float32 ones rounded, also after its tree was freed.
"""

import dataclasses

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from chipbench import families, harness, weights
from chipbench.tests import tiny, tiny_moe
from repro.launch import serve
from repro.models.registry import build_model

CONFIGS = {"dense": tiny.CONFIG, "moe": tiny_moe.CONFIG}
SEED = 11
STEPS = 4


def to(tree, dtype):
    """The tree with every floating-point leaf cast to ``dtype``."""
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def floats(tree):
    return [x for x in jax.tree.leaves(tree)
            if jnp.issubdtype(x.dtype, jnp.floating)]


def bits(x):
    """The array's bits, so that equal means bit for bit (NaNs too)."""
    x = np.asarray(x)
    return x.view(f"uint{8 * x.dtype.itemsize}") if x.dtype.kind == "f" \
        else x


def program_config(family):
    config = CONFIGS[family]
    return families.arch(config).program_config(config)


def rung_model(family, rung):
    window = CONFIGS[family]["serving"]["fast"]["sliding_window"]
    return build_model(serve.rung_configs(program_config(family),
                                          window)[rung])


@pytest.mark.parametrize("cfgs, held", [
    (dict(dtype="bfloat16", param_dtype="float32"), jnp.bfloat16),
    (dict(dtype="float32", param_dtype="float32"), jnp.float32),
    (dict(dtype="float32", param_dtype="bfloat16"), jnp.bfloat16),
    (dict(dtype="bfloat16", param_dtype="bfloat16"), jnp.bfloat16),
])
def test_held_dtype_is_the_narrower_of_the_two(cfgs, held):
    cfg = dataclasses.replace(program_config("dense"), **cfgs)
    assert serve.held_dtype(cfg) == held
    for rung in serve.rung_configs(cfg, 4).values():
        assert rung.parameter_dtype == held
        assert rung.dtype == cfgs["dtype"]


@pytest.mark.parametrize("rung", serve.RUNGS)
@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_bf16_parameters_give_the_same_bits(family, rung):
    """Prefill and four greedy decode steps of one rung, on float32
    parameters and on the same parameters cast to bfloat16: the logits and
    every leaf of the decode state (caches, positions, expert counters)
    agree bit for bit."""
    m = rung_model(family, rung)
    f32 = weights.make(families.arch(CONFIGS[family]), SEED)
    bf16 = to(f32, jnp.bfloat16)
    assert {x.dtype for x in floats(f32)} == {jnp.dtype(jnp.float32)}
    t = tiny.TRAFFIC
    prompt = jnp.asarray(harness.prompts(SEED, 1, t["batch"],
                                         t["prompt_len"], 512)[0])
    cache_len = m.cache_len_for(t["prompt_len"] + STEPS)
    prefill = jax.jit(lambda p, x: m.prefill(p, {"tokens": x},
                                             cache_len=cache_len))
    decode = jax.jit(m.decode_step)

    def run(params):
        logits, state = prefill(params, prompt)
        out = [(logits, state)]
        for _ in range(STEPS):
            logits, state = decode(params, state,
                                   jnp.argmax(logits, -1).astype(jnp.int32))
            out.append((logits, state))
        return out

    for i, (a, b) in enumerate(zip(run(f32), run(bf16))):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(bits(x), bits(y), err_msg=f"{i}")


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_held_parameters_are_not_cast(family, program):
    """In each program's jaxpr every float32 parameter is rounded to
    bfloat16 at the top; a parameter at the held dtype is rounded nowhere
    (the final norm's scale is still widened to float32, as it was)."""
    m = rung_model(family, "accurate")
    f32 = build_model(program_config(family)).abstract_params()
    held = m.abstract_params()
    assert {x.dtype for x in floats(held)} == {jnp.dtype(jnp.bfloat16)}
    tok = jax.ShapeDtypeStruct((2, 8), jnp.int32)

    def prefill(p, t):
        return m.prefill(p, {"tokens": t}, cache_len=16)

    def converted(params):
        if program == "prefill":
            fn, args = prefill, (params, tok)
        else:
            state = jax.eval_shape(prefill, params, tok)[1]
            fn = m.decode_step
            args = (params, state, jax.ShapeDtypeStruct((2,), jnp.int32))
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        n_params = len(jax.tree.leaves(params))
        ins = set(jaxpr.invars[:n_params])
        return sum(e.primitive.name == "convert_element_type"
                   and e.invars[0] in ins
                   and (e.params["new_dtype"].itemsize
                        < e.invars[0].aval.dtype.itemsize)
                   for e in jaxpr.eqns)

    assert converted(f32) == len(floats(f32))
    assert converted(held) == 0


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def bf16_plane(request):
    config = CONFIGS[request.param]
    arch = families.arch(config)
    t = tiny.TRAFFIC
    plane = serve.ServingPlane(
        arch.program_config(config),
        mesh=serve.build_mesh(devices=jax.devices()[:1]),
        window=config["serving"]["fast"]["sliding_window"],
        batch=t["batch"], prompt_len=t["prompt_len"],
        max_new=t["new_tokens"])
    return arch, plane


def test_bf16_plane_holds_bf16(bf16_plane):
    """The plane's own parameters are bfloat16, and equal the float32
    initializer's rounded: a float32 plane of the same config holds the
    masters of the values a bfloat16 plane serves."""
    _, plane = bf16_plane
    assert {x.dtype for x in floats(plane.params)} == {
        jnp.dtype(jnp.bfloat16)}
    want = to(build_model(plane.cfg).init(jax.random.PRNGKey(serve.SEED)),
              jnp.bfloat16)
    for got, w in zip(jax.tree.leaves(plane.params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(bits(got), bits(w))


def test_bf16_plane_compiles_for_bf16_parameters(bf16_plane):
    _, plane = bf16_plane
    for name in serve.RUNGS:
        for fn in (plane.prefill_fns[name], plane.step_fns[name]):
            params_avals = fn.in_avals[0][0]
            assert {a.dtype for a in floats(params_avals)} == {
                jnp.dtype(jnp.bfloat16)}
            assert (jax.tree.structure(params_avals)
                    == jax.tree.structure(plane.params))


def test_install_gives_the_seeds_weights_rounded(bf16_plane):
    """``weights.install`` makes the tree in the dtypes the plane holds:
    the float32 weights of ``weights.make`` rounded to bfloat16."""
    arch, plane = bf16_plane
    weights.install(plane, arch, SEED)
    got = weights.tree_paths(plane.params)
    want = weights.tree_paths(weights.make(arch, SEED))
    assert sorted(got) == sorted(want)
    for path, w in want.items():
        assert got[path].dtype == jnp.bfloat16, path
        rounded = np.asarray(w).astype(ml_dtypes.bfloat16)
        np.testing.assert_array_equal(bits(got[path]), bits(rounded),
                                      err_msg=str(path))


def test_param_bytes_is_the_held_tree(bf16_plane):
    """The call's ``param_bytes`` is the bytes of the bfloat16 tree, half
    the float32 store's, and the call line prints it."""
    _, plane = bf16_plane
    t = tiny.TRAFFIC
    res = plane.generate(plane.prompt(t["batch"], t["prompt_len"]), 3)
    held = sum(x.nbytes for x in jax.tree.leaves(plane.params))
    store = sum(x.size * 4 for x in
                jax.tree.leaves(plane.models["accurate"].abstract_params()))
    assert res.counters["param_bytes"] == held == store // 2
    assert f"; param_bytes {held}" in serve.call_line(res)


def test_install_after_the_tree_was_freed(bf16_plane):
    """As ``chipbench/tools/calibrate.py`` does between seeds: the plane's
    tree is freed for the reference, then the next seed's weights are
    installed from the plane's own abstract parameters, and its compiled
    programs take them."""
    arch, plane = bf16_plane
    t = tiny.TRAFFIC
    for seed in (SEED, SEED + 1):
        plane.params = None
        weights.install(plane, arch, seed)
        assert {x.dtype for x in floats(plane.params)} == {
            jnp.dtype(jnp.bfloat16)}
        res = plane.generate(plane.prompt(t["batch"], t["prompt_len"]), 3)
        assert res.logits.shape[0] == 3
        assert res.counters["param_bytes"] == plane.param_bytes

