"""Busiest expert's choices over the mean expert's, from the window's
``choices`` (live tokens' choices per expert, summed over the layers): 1.0
is an even router; the grouped matmul's longest group, and with ep > 1 the
busiest chip, scale with it. Through the run's family
(``benchmark/families/<runner>.py expert_load_imbalance``)."""
from benchmark import families


def read(run):
    return families.read(run, "expert_load_imbalance")
