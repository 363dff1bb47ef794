"""Median per call of ``ServeResult.prefill_s``: both rungs' prefill, to
``block_until_ready``."""

import statistics


def read(rec):
    return statistics.median(c.prefill_s * 1e3 for c in rec.calls)
