"""Device time of the decode program's executions in the traced stretch /
decode steps in it (executions x steps per dispatch)."""


def read(run):
    n, seconds = run["trace"].get("programs", {}).get("decode", (0, 0.0))
    if not n:
        return None
    return 1e3 * seconds / (n * run["serve_cfg"]["decode_steps_per_dispatch"])
