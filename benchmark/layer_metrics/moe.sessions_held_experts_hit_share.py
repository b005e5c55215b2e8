"""``moe.held_experts_hit_share`` in the sessions cell (40 of the router's
320 experts held, 8 a token, 64 slots: 1.6 tokens a held expert a step):
the share of the (expert layer, step, held expert) triples in which some
live token chose the expert, over the window. An entry of its own because
an accepted entry's list of cells is not a later PR's to lengthen. The same
reader (the experts held are ``n_routed_experts`` in both files)."""
from benchmark import layer_metrics

read = layer_metrics.load("moe.held_experts_hit_share").read
