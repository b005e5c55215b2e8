"""``engine.prefill_ride_token_share`` in the parallel cell
(``chat-batch-128``: a closed loop of 256 callers over 128 full slots,
prompts of 64-2,048 tokens, every reply begins with a prefill): the share of
the window's prefilled prompt tokens that rode the decode dispatches, each
piece attending over its slot's pages and scanning from its slot's own
state in every layer. An entry of its own because an accepted entry's list
of cells is not a later PR's to lengthen. The same reader."""
from benchmark import layer_metrics

read = layer_metrics.load("engine.prefill_ride_token_share").read
