"""The cross-chip merge of the sharded search: device milliseconds per batch
of the collective operations (the histogram and output psums, the count
all-gather) on the first chip."""
from harness import trace

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(run):
    tr = run["trace"]
    if tr["chips"] < 2:
        return None
    t = trace.op_seconds(tr, COLLECTIVES, chip=0)
    if t <= 0:
        return None
    return 1e3 * t / run["batches"]
