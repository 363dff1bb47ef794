"""Output tokens per second: batch x new tokens over every call of the
window, over the whole window (first call's start to last call's
return)."""


def read(rec):
    return rec.batch * rec.new_tokens * len(rec.calls) / rec.window_s
