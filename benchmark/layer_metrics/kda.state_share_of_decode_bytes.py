"""Recurrent-state bytes a decode step moves (live slots' state read and
written in every ``K`` layer) as a share of the step's byte floor
(``flops_linear.decode_step_bytes``): how much of the step is the second
kind of cache state."""
from benchmark import linear_counters


def read(run):
    moved = linear_counters.decode_step_bytes(run)
    if moved is None:
        return None
    return 100.0 * moved[0] / moved[1]
