"""``engine.prefill_ride_token_share`` in the hybrid cell
(``reason-batch-128``: a closed loop of 128 callers over 64 full slots,
prompts of 32-512 tokens, every reply begins with a prefill): the share of
the window's prefilled prompt tokens that rode the decode dispatches, each
piece one chunk of 128 rows run from its slot's own state-space state. An
entry of its own because an accepted entry's list of cells is not a later
PR's to lengthen; 0 on a program whose hybrid engine does not ride (before
PR 44: it has the counter and never rides). The same reader."""
from benchmark import layer_metrics

read = layer_metrics.load("engine.prefill_ride_token_share").read
