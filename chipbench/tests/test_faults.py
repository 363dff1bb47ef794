"""A run with the timed path broken underneath comes out not correct.

The tiny cell runs through the harness on the CPU (its look for a chip is
what ``run.py`` does, and is skipped here); the plane it builds has one
fault planted in its decode step, as a fault of the program would sit:

- a step that returns its state unchanged (the cache never grows);
- half of the batch left out (the second half's rows are not computed
  and carry the first half's logits);
- a token altered where it is produced (one vocabulary entry pushed to
  the top on every step);
- a schedule that serves more of each answer on the fast rung than the
  mix states (the program's own queue-depth spike widened).

The exchange between chips does not exist on one chip.  A sound run of
the same cell and seed comes out correct.
"""


import pytest

from chipbench import harness
from chipbench.tests import tiny


def state_unchanged(fn):
    import jax
    import jax.numpy as jnp

    def step(params, state, token):
        logits, _ = fn(params, jax.tree.map(jnp.copy, state), token)
        return logits, state
    return step


def half_batch(fn):
    def step(params, state, token):
        logits, state = fn(params, state, token)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), state
    return step


def token_altered(fn):
    def step(params, state, token):
        logits, state = fn(params, state, token)
        return logits.at[:, 7].add(100.0), state
    return step


def wider_spike(monkeypatch):
    """The program's spike covers the last two thirds of every answer."""
    from repro.launch import serve

    monkeypatch.setattr(serve, "queue_depth",
                        lambda i, n: 10 if i >= n // 3 else 0)


def run(monkeypatch, tmp_path, fault=None, seed=2**31 + 77):
    import jax

    build = harness.build_plane

    def broken(*args, **kw):
        plane, arch = build(*args, **kw)
        if fault:
            for rung, fn in list(plane.step_fns.items()):
                plane.step_fns[rung] = fault(fn)
        return plane, arch

    monkeypatch.setattr(harness, "build_plane", broken)
    result, compared = harness.run_cell(
        tiny.cell(), seed=seed, seconds=0.6, trace=False,
        devices=jax.devices()[:1], t_process=0.0, root=tmp_path)
    return result, compared


def test_sound_run_is_correct(monkeypatch, tmp_path):
    result, compared = run(monkeypatch, tmp_path)
    assert result["correct"], compared
    assert result["failed"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_fault_is_not_correct(monkeypatch, tmp_path, fault):
    result, compared = run(monkeypatch, tmp_path, fault)
    assert not result["correct"], compared


def test_more_fast_tokens_is_not_correct(monkeypatch, tmp_path):
    wider_spike(monkeypatch)
    result, compared = run(monkeypatch, tmp_path)
    assert not result["correct"], compared
    assert compared["off_schedule"]["value"] > 0
