"""Recurrent-state bytes a decode step moves (live slots' state read and
written in every state-space layer) as a share of the step's byte floor
(the family's ``decode_step_bytes``): how much of the step is the second
kind of cache state. Through the run's family
(``benchmark/families/<runner>.py ssm_state_share_of_decode_bytes``)."""
from benchmark import families


def read(run):
    return families.read(run, "ssm_state_share_of_decode_bytes")
