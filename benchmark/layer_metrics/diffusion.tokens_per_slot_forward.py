"""Tokens fixed a forward of a live slot, over the window: the engine's
``tokens_fixed`` / ``slot_forwards`` (``stats()["diffusion"]``, counted on
the device). The schedule alone gives ``block_length`` tokens in
``denoising_steps + 1`` forwards (0.8 at 4 and 4); rows fixed by the
confidence threshold raise it, a first window that holds a prompt's last
tokens lowers it."""
from benchmark import diffusion_counters


def read(run):
    return diffusion_counters.ratio(run, "tokens_fixed", "slot_forwards")
