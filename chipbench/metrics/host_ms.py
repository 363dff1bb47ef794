"""Median per call of the time outside the program's own prefill and
decode spans: logits to the host, stacking, the call's set-up."""

import statistics


def read(rec):
    return statistics.median((c.end - c.start - c.prefill_s - c.decode_s) * 1e3
                             for c in rec.calls)
