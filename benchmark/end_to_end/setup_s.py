"""Process start to the first instant of the measured window."""


def read(run):
    return run["setup_s"]
