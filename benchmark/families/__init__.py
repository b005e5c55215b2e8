"""One file per family of runs: ``families/<runner>.py``, found by the name
of the runner that produced the run (``run["runner"]``, which ``run.py``
stamps on the run: the traffic kind's first word, as it picks the runner).
A family's file gives, under the same few names, what the shared per-layer
readers need of a run of that family, each ``f(run) -> float | None``: the
bytes a step must move (``decode_step_bytes``, ``expert_bytes``,
``kv_bytes_per_token``, ``live_kv_tokens``), its own times
(``decode_step_ms``, ``moe_gmm_step_s``, ``paged_attention_ms_per_decode_step``)
and the readings whose whole body is the family's
(``held_experts_hit_share``, ``mla_attention_roofline_share``, ...). The
bytes come from the family's ``flops_*`` module and the counters from its
``*_counters`` module; this is the ONE place that says which.

A later cell of a new family ADDS a file here and appends its cell to the
entries' lists in ``BENCHMARK.json``; no reader and no file that is here is
edited. A family may start from another's names (``moe.py`` takes
``serve.py``'s). Never resolved from a cell's or a configuration's name."""
from benchmark.readers import loader

load = loader(__path__[0])


def of(run: dict):
    """The family module of the run, or None (a run no runner stamped, a
    runner with no family file: the training runner)."""
    name = run.get("runner")
    if not name:
        return None
    try:
        return load(name)
    except FileNotFoundError:
        return None


def read(run: dict, name: str):
    """``name(run)`` of the run's family; None where the family has no such
    name: the reader then leaves its metric out."""
    f = getattr(of(run), name, None)
    return f(run) if f else None
