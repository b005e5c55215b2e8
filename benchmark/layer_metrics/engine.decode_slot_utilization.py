"""Share of decode slot-steps that carried a request, over the window:
1 - padded_slot_steps / (decode_steps * slots), from engine.stats() deltas."""


def read(run):
    a, b = run["stats"]["before"], run["stats"]["after"]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    padded = b["padded_slot_steps"] - a["padded_slot_steps"]
    return 100.0 * (1.0 - padded / (steps * run["serve_cfg"]["max_batch_size"]))
