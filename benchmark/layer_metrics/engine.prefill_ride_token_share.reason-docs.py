"""``engine.prefill_ride_token_share`` in the linear cell
(``reason-docs-128``: a closed loop of 256 callers over 128 full slots,
questions of ~256 tokens and a document of 6-12k tokens in front of one in
sixteen): the share of the window's prefilled prompt tokens that rode the
decode dispatches, each piece a window from its slot's own delta-rule
state. An entry of its own because an accepted entry's list of cells is not
a later PR's to lengthen; 0 on a program whose delta-rule engine does not
ride (before PR 43: it has the counter and never rides). The same reader."""
from benchmark import layer_metrics

read = layer_metrics.load("engine.prefill_ride_token_share").read
