"""Share of decode steps that replay tokens a rung missed while the other
served (``ServeResult.catch_up_steps`` over all decode steps)."""


def read(rec):
    replay = sum(c.catch_up_steps for c in rec.calls)
    steps = sum(len(c.rungs) + c.catch_up_steps for c in rec.calls)
    return 100.0 * replay / steps
