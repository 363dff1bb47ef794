"""Set-up: process start to the window's start (compile-cache loads,
weights made on the device, prompts placed, the warm-up call)."""


def read(rec):
    return rec.setup_s
