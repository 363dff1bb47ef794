"""The control, the reference computed in fp8 and put in the program's
place, fails the check where the bf16 program passes it: at the tiny
cell's size on the CPU, on three seeds, against the tiny cell's limits.
On the chip the same readings at each cell's own size set its limits
(``tools/calibrate.py``; the readings are in ``limits/<cell>.json``)."""

import pytest

from chipbench import harness
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def built():
    import jax

    cell = tiny.cell()
    plane, arch = harness.build_plane(cell, jax.devices()[:1], 1)
    return cell, plane, arch


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_program_passes(built, seed):
    import jax

    cell, plane, arch = built
    t = cell.traffic
    harness.weights.install(plane, arch, seed)
    host = harness.prompts(seed, 2, t["batch"], t["prompt_len"], arch.vocab)
    got = harness.collect(
        [harness.answer(plane.generate(jax.device_put(x, plane.tokens_sh),
                                       t["new_tokens"])) for x in host],
        host, t)
    r = harness.compare(cell, arch, seed, got, control=True)
    limits = {k: v["limit"] for k, v in cell.limits.items()}
    assert all(r[k] <= limits[k] for k in limits), r
    assert any(r[f"control.{k}"] > limits[k] for k in limits), r
