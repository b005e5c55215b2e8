"""Share of the window's prompt tokens that came from the prefix cache, for
an engine that drafts: the accepted ``kv.prefix_cached_token_share`` (the
window's delta of ``prefix_cached_tokens`` over that of
``prefix_cached_tokens`` + ``prefill_tokens``) under this cell's name. ~96
where the system prompts are resident (a request computes its last partial
page and its task); lower means a system prompt was evicted or a hash
missed."""
from benchmark import selfdraft_counters
from benchmark.layer_metrics import load

_share = load("kv.prefix_cached_token_share")


def read(run):
    return _share.read(run) if selfdraft_counters.is_selfdraft(run) else None
