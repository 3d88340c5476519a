"""The LM side of the kNN-LM server: device milliseconds per search call
(``batches``: decode steps and admission prefills) outside the search kernels
(``hamming_hist_pallas``, ``hamming_emit_pallas``): the decode and prefill
of the model, the query encode, the vocabulary scatter and the mix."""
from harness import trace

SEARCH = ("hamming_hist_pallas", "hamming_emit_pallas")


def read(run):
    tr = run["trace"]
    if tr["busy_s"] <= 0 or not run.get("batches"):
        return None
    return 1e3 * (tr["busy_s"] - trace.op_seconds(tr, SEARCH)) / run[
        "batches"]
