"""``engine.prefill_ride_token_share`` in the short-conv cell
(``assist-batch-256``: a closed loop of 512 callers over 256 full slots,
prompts of 32-1,024 tokens, every reply begins with a prefill): the share
of the window's prefilled prompt tokens that rode the decode dispatches,
each piece attending over its slot's pages and convolving from its slot's
own two rows. Near the riding path's limit here: ~190 of the 256 rows a
step can carry. An entry of its own because an accepted entry's list of
cells is not a later PR's to lengthen. The same reader."""
from benchmark import layer_metrics

read = layer_metrics.load("engine.prefill_ride_token_share").read
