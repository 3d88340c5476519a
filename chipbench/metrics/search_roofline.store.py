"""The whole search's share of its roofline: the least time of the batches
searched in the window (``harness/work.py``: 2*Q*N*d int8 operations or
N*d/8 bytes, whichever bounds) over the device's busy time in the window."""


def read(run):
    busy = run["trace"]["busy_s"]
    if busy <= 0:
        return None
    return 100.0 * run["least_time_per_batch_s"] * run["batches"] / busy
