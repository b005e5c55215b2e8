"""Device time of the hyper-connections in the decode program: the
operations under the named scopes ``hc_maps`` (the phi product, the
sigmoids, the Sinkhorn iterations) and ``hc_mix`` (H_pre @ X, H_res @ X +
H_post (x) F) in the runner's by-scope seconds / decode steps on the device
(by scope, as ``kernels.ssm_decode_ms_per_decode_step`` reads
``ssm_decode``). XLA may fuse a scope's operations into a neighbour's
fusion, which then counts under the scope its root instruction names."""
from benchmark import latent_counters


def read(run):
    parts = [latent_counters.scope_seconds(run, s)
             for s in ("hc_maps", "hc_mix")]
    steps = latent_counters.traced_decode_steps(run)
    if not steps or all(p is None for p in parts):
        return None
    return 1e3 * sum(p or 0.0 for p in parts) / steps
