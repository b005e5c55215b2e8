"""Share of the window's prompt tokens that came from the prefix cache:
the window's delta of ``prefix_cached_tokens`` over that of
``prefix_cached_tokens`` + ``prefill_tokens`` (``engine.stats()``). Near 99
in a cell whose documents are resident; lower means the cell is not
measuring what it says (a document was evicted, or a hash missed)."""


def read(run):
    a, b = run["stats"]["before"], run["stats"]["after"]
    if "prefix_cached_tokens" not in a or "prefill_tokens" not in a:
        return None
    cached = b["prefix_cached_tokens"] - a["prefix_cached_tokens"]
    computed = b["prefill_tokens"] - a["prefill_tokens"]
    if cached + computed <= 0:
        return None
    return 100.0 * cached / (cached + computed)
