"""Share of the block tables' pages that the paged-attention kernel walks,
over the window: the engine's ``live_pages`` / ``table_pages``
(``engine.stats()["kv"]``; counted once a decode dispatch: the pages the
slots' lengths cover at its first step, an idle slot's one page among them,
against slots x pages a slot). Low where the kernel's time is its fixed cost
a slot, near 100 where it is the pages' bytes. A program without the
counters (a parent commit from before them) gives None."""


def read(run):
    a, b = run["stats"]["before"]["kv"], run["stats"]["after"]["kv"]
    if "table_pages" not in a or "table_pages" not in b:
        return None
    table = b["table_pages"] - a["table_pages"]
    if table <= 0:
        return None
    return 100.0 * (b["live_pages"] - a["live_pages"]) / table
