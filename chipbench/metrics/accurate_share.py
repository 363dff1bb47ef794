"""Share of output tokens the accurate rung served (``ServeResult.rungs``):
Compass's accuracy.  A change that buys speed by serving more tokens on
the fast rung shows here."""


def read(rec):
    rungs = [r for c in rec.calls for r in c.rungs]
    return 100.0 * rungs.count("accurate") / len(rungs)
