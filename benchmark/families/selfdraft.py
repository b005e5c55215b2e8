"""Runs of ``runners/selfdraft.py`` (a prediction module drafts, the main
stack verifies: a "step" is a draft-and-verify step of two rows a slot):
bytes from ``flops_selfdraft.py``, the decode program's by-scope seconds and
the ``mtp_*`` / ``kv`` / ``moe`` counters from ``selfdraft_counters.py``. A
program that does not draft (the parent of PR 53) gives None throughout."""
from benchmark import families, flops, flops_selfdraft, selfdraft_counters

_latent = families.load("latent")
decode_step_ms = selfdraft_counters.step_ms
decode_step_bytes = selfdraft_counters.step_bytes


def _drafts(read):
    return lambda run: (read(run) if selfdraft_counters.is_selfdraft(run)
                        else None)


# the experts held are ``n_routed_experts``, the module's expert layer
# among the layers
held_experts_hit_share = _drafts(
    families.load("hybrid").held_experts_hit_share)
mla_live_page_share = _drafts(_latent.mla_live_page_share)
prefix_cached_token_share = _drafts(_latent.prefix_cached_token_share)


def moe_gmm_step_s(run):
    """``moe_gmm`` and ``moe_gmm_prefill`` of the decode program: whichever
    the window takes."""
    gmm_ms = selfdraft_counters.step_scope_ms(
        run, "moe_gmm", "moe_gmm_prefill")
    return gmm_ms * 1e-3 if gmm_ms else None


def expert_bytes(run):
    """The held experts some live row of the window chose."""
    hit = selfdraft_counters.experts_hit_per_step(run)
    return (None if hit is None
            else flops_selfdraft.expert_bytes(run["config"], hit))


def mla_attention_roofline_share(run):
    """Of the window kernel (``mla_paged_attention_mq`` at two rows a
    slot), over the cached layers (the module's too): each live latent page
    read ONCE for the window's two rows, two rows' scores and values."""
    kernel_ms = selfdraft_counters.step_scope_ms(
        run, "mla_paged_attention_mq")
    pages = selfdraft_counters.live_pages_per_step(run)
    ps = selfdraft_counters.page_size(run)
    if not kernel_ms or pages is None or ps is None:
        return None
    cfg, peaks = run["config"], flops.peaks(run["device"]["kind"])
    floor_s = flops_selfdraft.cached_layers(cfg) * max(
        flops_selfdraft.kernel_bytes(cfg, pages, ps)
        / peaks["hbm_bytes_per_s"],
        flops_selfdraft.kernel_flops(cfg, pages * ps)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
