"""Recurrent-state bytes a decode step moves (live slots' state read and
written in every layer) as a share of the step's byte floor
(``flops_parallel.decode_step_bytes``), from the engine's counters over the
traced stretch: how much of the step is the second kind of cache state."""
from benchmark import flops_parallel, parallel_counters


def read(run):
    slots = parallel_counters.live_slots_per_step(run)
    total = parallel_counters.decode_step_bytes(run)
    if slots is None or not total:
        return None
    return (100.0 * flops_parallel.state_step_bytes(run["config"], slots)
            / total)
