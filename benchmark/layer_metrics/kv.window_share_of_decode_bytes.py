"""Share of a decode step's byte floor that is the window layers' visible K
and V rows (over shared weights + hit experts + the full layers' live rows +
those rows + the rows written), traced stretch: how much of the step the
window layers' cache still is. None for a program without a ``window``
group."""
from benchmark import families


def read(run):
    return families.read(run, "window_share_of_decode_bytes")
