"""Tokens of the steps between the window's fences / that time / chips."""
from benchmark import facts


def read(run):
    tokens, seconds = facts.train_rate(run)
    return tokens / seconds / run["chips"]
