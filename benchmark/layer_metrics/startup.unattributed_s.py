"""``setup_s`` less what the program names: every ``llmctl.startup.*``
span's self seconds and, in a serving cell, the engine thread's work before
the window. What is left is the benchmark's own (interpreter start, its
weight init, the plain reference, the load generator's child) and whatever
the program does under no span."""
from benchmark import startup_counters


def read(run):
    named = startup_counters.named_seconds(run)
    if named is None:
        return None
    work = startup_counters.engine_work_seconds(run) or 0.0
    return run["setup_s"] - named - work
