"""Pass 1 of the search (the histogram race, ``hamming_hist_pallas``): the
search's least time per batch over pass 1's device time per batch. Pass 1
reads every code and scores every pair, so the whole search's least time is
its own."""
from harness import trace

KERNEL = ("hamming_hist_pallas",)


def read(run):
    t = trace.op_seconds(run["trace"], KERNEL)
    if t <= 0:
        return None
    return 100.0 * run["least_time_per_batch_s"] * run["batches"] / t
