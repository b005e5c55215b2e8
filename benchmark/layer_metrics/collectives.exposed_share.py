"""Share of the traced stretch in which a collective ran on device 0 and no
other operation did."""


def read(run):
    t = run["trace"]
    if not t or run["chips"] < 2:
        return None
    return 100.0 * t["exposed_collective_s"] / t["window_s"]
