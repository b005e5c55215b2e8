"""Share of the window's prompt tokens that came from the prefix cache:
the window's delta of ``prefix_cached_tokens`` over that of
``prefix_cached_tokens`` + ``prefill_tokens`` (``engine.stats()``). Near 99
in a cell whose documents are resident, ~96 where system prompts are (a
request computes its last partial page and its task); lower means the cell
is not measuring what it says (a document was evicted, or a hash missed).
Through the run's family (``benchmark/families/<runner>.py
prefix_cached_token_share``)."""
from benchmark import families


def read(run):
    return families.read(run, "prefix_cached_token_share")
