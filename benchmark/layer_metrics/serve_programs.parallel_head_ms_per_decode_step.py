"""Device time of the head a decode step: the [slots, 5120] x [5120,
261120] matmul (``lm_head``) and the sampler's passes over the 261,120
float32 logits a slot (``sampler``: the argmax of greedy traffic), in the
decode program, in the traced stretch / decode steps on the device."""
from benchmark import parallel_counters


def read(run):
    return parallel_counters.decode_scope_ms_per_step(run, "lm_head",
                                                      "sampler")
