"""Mean time a step waited in ``next(engine.train_data)``."""


def read(run):
    waits = run["data_wait_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None
