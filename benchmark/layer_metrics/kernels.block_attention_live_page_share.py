"""Share of the block tables' pages that the block-rule page kernel walks
in a forward, over the window: the DEVICE's own count (``stats()
["diffusion"]["live_pages"]``: pages up to the window's end, summed over
the live slots of every forward inside ``denoise_scan``) over forwards x
slots x pages a slot. Low where the kernel's time is its fixed cost a slot
(64 grid steps of a few pages each), near 100 where it is the pages'
bytes."""
import math

from benchmark import diffusion_counters


def read(run):
    pages = diffusion_counters.ratio(run, "live_pages", "forwards")
    ps = diffusion_counters.page_size(run)
    if pages is None or not ps:
        return None
    table = (math.ceil(run["config"]["serve"]["max_seq_len"] / ps)
             * run["serve_cfg"]["max_batch_size"])
    return 100.0 * pages / table
