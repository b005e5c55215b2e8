"""Device time of the denoise forward's grouped expert matmuls (the
decode program's ``moe_gmm_prefill*``: a window of ``block_length`` rows a
slot takes the kernel's windowed form; three calls a layer over slots x
``block_length`` rows x ``num_experts_per_tok`` choices) in the traced
stretch / forwards in it. The prefill programs' kernels of the same name
are not counted."""
from benchmark import diffusion_counters


def read(run):
    return diffusion_counters.kernel_ms_per_forward(run, "moe_gmm_prefill")
