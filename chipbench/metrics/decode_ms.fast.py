"""Median device time of one decode step of the fast rung (served and
catch-up steps alike), from the profiler trace of the traced window."""


def read(rec):
    return rec.decode_ms("fast")
