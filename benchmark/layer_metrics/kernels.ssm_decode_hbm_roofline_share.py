"""Least time the one-step state update could take (every live slot's state
read once and written once in every state-space layer, over the HBM peak)
as a share of ``ssm_decode``'s measured time a step. Bytes are the measure:
a slot's update is 4 operations an element of its state. Through the run's
family (``benchmark/families/<runner>.py ssm_decode_hbm_roofline_share``)."""
from benchmark import families


def read(run):
    return families.read(run, "ssm_decode_hbm_roofline_share")
