"""Output tokens delivered to the clients inside the window per second."""
from benchmark import facts


def read(run):
    w0, w1 = run["window"]
    return facts.tokens_in_window(run) / (w1 - w0)
