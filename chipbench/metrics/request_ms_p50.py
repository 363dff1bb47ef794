"""50th percentile, over every request of the window, of its latency: its
call's return less its call's start (a call serves its batch together)."""


def read(rec):
    return rec.request_ms(50)
