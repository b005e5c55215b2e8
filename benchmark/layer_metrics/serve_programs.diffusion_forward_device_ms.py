"""Device time of the decode program's executions in the traced stretch /
forwards in it (executions x forwards a dispatch): one pass of every slot's
window of ``block_length`` rows through the layers, the head and the
transfer rule."""
from benchmark import diffusion_counters


def read(run):
    return diffusion_counters.forward_device_ms(run)
