"""Share of the roofline reached by the accurate rung's decode steps
(``costs.decode_roofline``): their least times over their device time."""

from chipbench import costs


def read(rec):
    return costs.decode_roofline(rec, "accurate")
