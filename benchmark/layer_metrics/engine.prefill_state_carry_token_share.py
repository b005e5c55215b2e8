"""Share of the window's prefilled prompt tokens that went through chunk
programs which read a slot's recurrent state and conv window
(``stats()["kda"]["state_carry_tokens"]`` over ``prefill_tokens``): the
documents' part of the prefill load."""
from benchmark import linear_counters


def read(run):
    return linear_counters.state_carry_token_share(run)
