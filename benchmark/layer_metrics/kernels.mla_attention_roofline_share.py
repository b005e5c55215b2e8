"""Least time the step's latent paged-attention kernel could take as a
share of its measured time: over the layers that cache latent rows, the
greater of the byte floor (each live latent page read once a slot a layer,
as stored, over the HBM peak) and the FLOP floor (absorbed scores and
values over the bf16 peak). Live pages are the engine's own count over the
traced stretch. Which layers, which kernel (the window kernel at two rows a
slot where the engine drafts) and which ``flops_*`` module are the run's
family's (``benchmark/families/<runner>.py mla_attention_roofline_share``)."""
from benchmark import families


def read(run):
    return families.read(run, "mla_attention_roofline_share")
