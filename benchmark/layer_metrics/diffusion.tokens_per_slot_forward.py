"""Tokens fixed a forward of a live slot, over the window: the engine's
``tokens_fixed`` / ``slot_forwards`` (``stats()["diffusion"]``, counted on
the device). The schedule alone gives ``block_length`` tokens in
``denoising_steps`` forwards (1.0 at 4 and 4) since PR 47, when a block's
commit began to ride the next block's first forward (0.8 before it, with a
forward for the commit alone); rows fixed by the confidence threshold raise
it, a first window that holds a prompt's last tokens lowers it."""
from benchmark import diffusion_counters


def read(run):
    return diffusion_counters.ratio(run, "tokens_fixed", "slot_forwards")
