"""Pass 2 of the search (the emit, ``hamming_emit_pallas``): device
milliseconds per batch."""
from harness import trace

KERNEL = ("hamming_emit_pallas",)


def read(run):
    t = trace.op_seconds(run["trace"], KERNEL)
    if t <= 0:
        return None
    return 1e3 * t / run["batches"]
