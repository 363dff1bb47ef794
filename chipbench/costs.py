"""Least times from operations and bytes, the table of peaks, and the
rooflines and served work built on them.

Each family counts its own steps' operations and bytes
(``families/<family>.py``: ``Arch.decode_cost``, ``Arch.prefill_cost``);
what is here holds for every family.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


@dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def least_s(self, peaks: dict) -> float:
        """The least time the chip could take: the larger of operations
        over peak bf16 rate and bytes over HBM bandwidth."""
        return max(self.flops / peaks["bf16_flops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, not a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def attended(pos: int, window: int) -> int:
    """Keys a query at 0-based ``pos`` attends, itself included."""
    return pos + 1 if window <= 0 else min(pos + 1, window)


def served_flops(a, *, batch: int, prompt_len: int,
                 positions: list) -> float:
    """Model operations of the work a call serves: the accurate rung's
    prefill once, and each output token at its own context and rung
    (``positions`` holds ``(pos, window)`` of every served decode step).
    The other rung's prefill and the catch-up replay are overhead and do
    not count."""
    f = a.prefill_cost(batch=batch, prompt_len=prompt_len, window=0,
                       kv_dtype="bfloat16").flops
    for pos, window in positions:
        f += a.decode_cost(batch=batch, pos=pos, window=window,
                           kv_dtype="bfloat16").flops
    return f


def decode_roofline(rec, rung: str):
    """Share of the roofline reached by ``rung``'s decode steps in the
    traced window: the least time of each step (``decode_cost`` at the
    step's position, window and cache dtype), summed, over the device time
    those steps took; ``None`` without a traced step of the rung."""
    if rec.trace is None:
        return None
    r = rec.serving[rung]
    timed = [(s, t) for s, t in rec.trace.timed
             if s.kind == "decode" and s.rung == rung]
    if not timed:
        return None
    least = sum(rec.arch.decode_cost(
        batch=rec.batch, pos=s.pos, window=int(r["sliding_window"]),
        kv_dtype=r["kv_cache_dtype"], dtype=rec.serving["dtype"]
    ).least_s(rec.peaks) for s, _ in timed)
    return 100.0 * least / sum(t for _, t in timed)
