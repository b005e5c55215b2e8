"""Least time the one-step delta-rule update could take (every live slot's
state read once and written once in every ``K`` layer, over the HBM peak) as
a share of ``kda_decode``'s measured time a step. Bytes are the measure: a
slot's update is a handful of operations an element of a 2 MB state. The
bytes are the run's family's (``benchmark/families/<runner>.py
kda_decode_hbm_roofline_share``: the sessions family counts the kernel's
operands too)."""
from benchmark import families


def read(run):
    return families.read(run, "kda_decode_hbm_roofline_share")
