"""``engine.prefill_ride_token_share`` in the sessions cell (``sessions-64``:
a closed loop of 128 sessions over 64 full slots, every turn ~640 new
tokens behind a snapshot hit): the share of the window's prefilled prompt
tokens that rode the decode dispatches, each piece a window from its
slot's own delta-rule state, the first from the state a snapshot armed. An
entry of its own because an accepted entry's list of cells is not a later
PR's to lengthen. The same reader."""
from benchmark import layer_metrics

read = layer_metrics.load("engine.prefill_ride_token_share").read
