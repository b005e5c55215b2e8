"""Device time under the prediction module's scopes (``mtp_embed_proj``,
``mtp_layer``, ``mtp_head``: its projection, its decoder layer over its own
latent rows and experts, its norm and head pass) as a share of a
draft-and-verify step's: what drafting costs beside verifying. The module
is 1 layer of 9 here and 1 of 41 in the deployment: ~4x its deployed
share."""
from benchmark import selfdraft_counters


def read(run):
    step_ms = selfdraft_counters.step_ms(run)
    module_ms = selfdraft_counters.step_scope_ms(
        run, "mtp_embed_proj", "mtp_layer", "mtp_head")
    if not step_ms or module_ms is None:
        return None
    return 100.0 * module_ms / step_ms
