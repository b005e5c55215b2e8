"""Mean time a seated request waited for its first token, where prompts
ride the decode steps: the window's ``prompt_wait`` slot-steps x the wall
ms a step / the window's first tokens. (A slot-step of waiting lasts one
step of the engine's wall clock; a request seated before the window and
armed in it counts its steps inside alone, as one armed after it does.)"""
from benchmark import slot_step_counters


def read(run):
    w = slot_step_counters.window(run)
    if w is None or not w["first_tokens"]:
        return None
    return (w["prompt_wait"] * 1e3 * w["clock_s"] / w["decode_steps"]
            / w["first_tokens"])
