"""Share of the rows the prefill programs computed that were prompt tokens,
over the window: the engine's ``prefill_tokens`` / ``prefill_padded_tokens``
(``engine.stats()``; the second counts the bucket of every cold or suffix
prefill and the chunk program's rows of a chunked one). The rest is the
padding up to the bucket ladder's next rung. A program without the counter
(a parent commit from before it) gives None."""


def read(run):
    a, b = run["stats"]["before"], run["stats"]["after"]
    if "prefill_padded_tokens" not in a or "prefill_padded_tokens" not in b:
        return None
    rows = b["prefill_padded_tokens"] - a["prefill_padded_tokens"]
    if rows <= 0:
        return None
    return 100.0 * (b["prefill_tokens"] - a["prefill_tokens"]) / rows
