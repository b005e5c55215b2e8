"""Share of a decode step's byte floor that is latent rows: live latent
pages' rows in every layer over (weights once + hit experts + those rows),
traced stretch. How much of the step the mechanism is."""
from benchmark import latent_counters


def read(run):
    moved = latent_counters.decode_step_bytes(run)
    if moved is None:
        return None
    return 100.0 * moved[0] / moved[1]
