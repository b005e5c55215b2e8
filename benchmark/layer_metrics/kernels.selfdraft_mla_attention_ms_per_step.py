"""Device time of the window's latent paged-attention kernel
(``mla_paged_attention_mq`` at two rows a slot: the main stack's layers and
the module's) in the decode program over the traced stretch / steps."""
from benchmark import selfdraft_counters


def read(run):
    return selfdraft_counters.step_scope_ms(run, "mla_paged_attention_mq")
