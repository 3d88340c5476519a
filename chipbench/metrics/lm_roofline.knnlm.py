"""The LM's share of its roofline in the kNN-LM server: the least time of
the window's LM work (``harness/lm_work.py``: each decode step the larger
of its bytes at the HBM bandwidth and its operations at the bf16 peak, each
prefill its operations at the bf16 peak; counts from the server's counters)
over the device time outside the search kernels."""
from harness import trace

SEARCH = ("hamming_hist_pallas", "hamming_emit_pallas")


def read(run):
    tr = run["trace"]
    lm_s = tr["busy_s"] - trace.op_seconds(tr, SEARCH)
    if lm_s <= 0 or not run.get("lm_least_time_s"):
        return None
    return 100.0 * run["lm_least_time_s"] / lm_s
