"""``engine.prefill_ride_token_share`` in the latent cell (``doc-qa-64``:
a closed loop over full slots, every prompt a prefix hit with a tail of
~225 tokens): the share of the window's prefilled prompt tokens that rode
the decode dispatches behind their documents' cached pages. An entry of
its own because an accepted entry's list of cells is not a later PR's to
lengthen; 0 on a program whose latent engine does not ride (before PR 41).
The same reader."""
from benchmark import layer_metrics

read = layer_metrics.load("engine.prefill_ride_token_share").read
