"""Runs of ``runners/latent.py`` (latent attention over latent pages, expert
layers fewer than decoder layers): bytes from ``flops_latent.py``, by-scope
seconds and the ``kv`` / ``moe`` counters from ``latent_counters.py``."""
from benchmark import families, flops, flops_latent, latent_counters

decode_step_ms = families.load("serve").decode_step_ms
_scope_ms = families.load("hybrid").scope_ms_per_decode_step


def decode_step_bytes(run):
    """Attention, dense feed-forward, router, shared-expert, head and map
    weights once, the experts some LIVE token chose once and every live
    latent page's rows in every layer (the engine's live-page count)."""
    moved = latent_counters.decode_step_bytes(run)
    return None if moved is None else moved[1]


def moe_gmm_step_s(run):
    """Every ``moe_gmm`` operation by name, not the ten longest."""
    s = latent_counters.scope_seconds(run, "moe_gmm")
    steps = latent_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return s / steps


def expert_bytes(run):
    """The counter is divided by the EXPERT layers, which are fewer than
    ``num_hidden_layers`` here."""
    hit = latent_counters.decode_experts_hit_per_step(run)
    return (None if hit is None
            else flops_latent.expert_bytes(run["config"], hit))


def mla_attention_ms_per_decode_step(run):
    return _scope_ms(run, "mla_paged_attention")


def mla_attention_roofline_share(run):
    """In every layer. Live pages are the engine's own count over the
    traced stretch (a page a slot's length covers), so the bytes cannot be
    counted high: a page's padding rows past the length are in them, as
    they are in the copy."""
    kernel_ms = mla_attention_ms_per_decode_step(run)
    pages = latent_counters.live_pages_per_step(run)
    ps = latent_counters.page_size(run)
    if not kernel_ms or pages is None or ps is None:
        return None
    cfg, peaks = run["config"], flops.peaks(run["device"]["kind"])
    layers = cfg["num_hidden_layers"]
    floor_s = layers * max(
        flops_latent.kernel_bytes(cfg, pages, ps) / peaks["hbm_bytes_per_s"],
        flops_latent.kernel_flops(cfg, pages * ps)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)


def mla_live_page_share(run):
    if not latent_counters.is_latent(run):
        return None
    a, b = run["stats"]["before"]["kv"], run["stats"]["after"]["kv"]
    table = b["table_pages"] - a["table_pages"]
    if table <= 0:
        return None
    return 100.0 * (b["live_pages"] - a["live_pages"]) / table


def prefix_cached_token_share(run):
    a, b = run["stats"]["before"], run["stats"]["after"]
    if "prefix_cached_tokens" not in a or "prefill_tokens" not in a:
        return None
    cached = b["prefix_cached_tokens"] - a["prefix_cached_tokens"]
    computed = b["prefill_tokens"] - a["prefill_tokens"]
    if cached + computed <= 0:
        return None
    return 100.0 * cached / (cached + computed)
