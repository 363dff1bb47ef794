"""The comparison that decides ``correct``.

The served tokens of a sample of the window's requests, drawn from the
seed, are held to the plain reference (``reference.py``) run once over
each prompt with the tokens the program fed back.  For every served token
the number read is its gap: how far the reference's logit of that token
lies below the reference's best at that position.  A greedy server that
computes what the reference computes serves the best token or one within
rounding of it; a wrong step, cache or token serves one far below.

Each rung is held to its own reference: the accurate rung (prefill and
its decode steps, those after the switch back included) to full causal
attention, the fast rung to its sliding window with the cache precision
its configuration states.  Which rung serves which step is stated by the
traffic mix (``schedule``), not taken from the program: a call whose
rungs, or whose count of replayed steps, differ from what the schedule
states is not correct, and each position is held to the reference of the
rung the schedule gives it.  The numbers compared are the widest gap of
each rung over the sample (``gap_accurate``, ``gap_fast``); their limits
are in ``limits/<cell>.json`` with the readings they were set from.
"""

from __future__ import annotations

import gc
import math
from typing import Dict, List, Optional

import numpy as np

from . import weights

SAMPLE_TOKENS = 512     # served tokens the sample holds at least


def sample(seed: int, n_requests: int, tokens_per_request: int) -> List[int]:
    """Indices of the requests to compare, drawn from the seed among the
    ``n_requests`` the window finished (all of one length)."""
    k = min(n_requests, max(1, math.ceil(SAMPLE_TOKENS / tokens_per_request)))
    rng = np.random.default_rng([int(seed), 1])
    return sorted(rng.choice(n_requests, size=k, replace=False).tolist())


def rung_reference(serving: dict, rung: str, prompt_len: int) -> dict:
    """The reference's keyword arguments for ``rung``."""
    r = serving[rung]
    return {"window": int(r["sliding_window"]),
            "kv_int8_from": prompt_len if r["kv_cache_dtype"] == "int8" else -1}


def reference_logits(a, serving: dict, w, prompts: np.ndarray,
                     fed: np.ndarray, rungs_used, *,
                     operand: Optional[str] = None) -> Dict[str, np.ndarray]:
    """``{rung: (R, n + 1, V)}``: the logits at positions P - 1 .. P + n - 1
    of each prompt followed by the n tokens fed back, under each rung's
    reference (``operand="fp8"``: the control)."""
    p = prompts.shape[1]
    seqs = np.concatenate([prompts, fed], axis=1)
    return {rung: a.logits(w, seqs, out_from=p - 1, operand=operand,
                           **rung_reference(serving, rung, p))
            for rung in rungs_used}


def stated_rungs(traffic: dict) -> List[str]:
    """The rung that serves each decode step, from the mix's ``schedule``
    (runs of ``[rung, steps]``), which has to cover ``new_tokens``."""
    rungs = [r for r, k in traffic["schedule"] for _ in range(int(k))]
    if len(rungs) != int(traffic["new_tokens"]):
        raise ValueError(f"mix {traffic.get('name')!r}: schedule covers "
                         f"{len(rungs)} steps, new_tokens is "
                         f"{traffic['new_tokens']}")
    return rungs


def replayed(rungs: List[str]) -> int:
    """Decode steps replayed at the switches of ``rungs``: a rung that
    becomes active first feeds every token it missed."""
    done: Dict[str, int] = {}
    n = 0
    for i, rung in enumerate(rungs):
        n += i - done.get(rung, 0)
        done[rung] = i + 1
    return n


def position_rungs(rungs: List[str]) -> List[str]:
    """The rung that served each of the n + 1 tokens of a request: the
    accurate prefill, then decode step by step."""
    return ["accurate"] + list(rungs)


def gaps(ref: Dict[str, np.ndarray], served: np.ndarray,
         pos_rungs: List[str]) -> Dict[str, float]:
    """Widest gap of each rung: ``max(ref) - ref[served]`` over the
    positions that rung served.  ``served``: (R, n + 1) token ids."""
    out = {}
    for rung in sorted(set(pos_rungs)):
        cols = [j for j, r in enumerate(pos_rungs) if r == rung]
        lg = ref[rung][:, cols]                               # (R, m, V)
        got = np.take_along_axis(lg, served[:, cols, None], axis=-1)[..., 0]
        out[f"gap_{rung}"] = float((lg.max(-1) - got).max())
    return out


def readings(a, serving: dict, seed: int, prompts: np.ndarray,
             fed: np.ndarray, served: np.ndarray, rungs: List[str], *,
             control: bool = False) -> Dict[str, float]:
    """The numbers compared for one sample: the program's widest gaps,
    and with ``control`` the control's (the reference in fp8, its own
    greedy tokens read in the float32 reference), under ``control.``.

    The weights are made again from the seed here; nothing the program
    made is used."""
    pos_rungs = position_rungs(rungs)
    w = weights.make(a, seed)
    ref = reference_logits(a, serving, w, prompts, fed, set(pos_rungs))
    out = gaps(ref, served, pos_rungs)
    if control:
        low = reference_logits(a, serving, w, prompts, fed, set(pos_rungs),
                               operand="fp8")
        picked = np.stack([low[r][:, j].argmax(-1)
                           for j, r in enumerate(pos_rungs)], axis=1)
        out.update({f"control.{k}": v
                    for k, v in gaps(ref, picked, pos_rungs).items()})
    del w
    gc.collect()
    return out
