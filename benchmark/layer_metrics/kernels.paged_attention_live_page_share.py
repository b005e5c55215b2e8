"""Share of the block tables' pages that the paged-attention kernel walks,
over the window: the engine's ``live_pages`` / ``table_pages``
(``engine.stats()["kv"]``; counted once a decode dispatch: the pages the
slots' lengths cover at its first step, an idle slot's one page among them,
against slots x pages a slot). Low where the kernel's time is its fixed cost
a slot, near 100 where it is the pages' bytes. A program without the
counters (a parent commit from before them) gives None. Through the run's
family (``benchmark/families/<runner>.py paged_attention_live_page_share``)."""
from benchmark import families


def read(run):
    return families.read(run, "paged_attention_live_page_share")
