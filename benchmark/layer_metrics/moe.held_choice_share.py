"""Share of the live tokens' expert choices that fell on experts HELD
here, over the window: ``held_choices`` / ``all_choices`` of
``engine.stats()["moe"]`` (about held / router width under a router that
spreads evenly)."""
from benchmark import moe_counters


def read(run):
    d = moe_counters.window(run)
    if not d or not d.get("all_choices"):
        return None
    return 100.0 * d["held_choices"] / d["all_choices"]
