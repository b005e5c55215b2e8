"""Device time of the block-rule page kernel (``paged_attention_blk`` in
the decode program: one call a layer, every slot's window of
``block_length`` rows over its live pages) in the traced stretch /
forwards in it. Read by operation name from the trace, not from the
ten-line ``device_ops`` list."""
from benchmark import diffusion_counters


def read(run):
    return diffusion_counters.kernel_ms_per_forward(run,
                                                    "paged_attention_blk")
