"""Operations and bytes that a search needs, computed from the algorithm's
shapes alone, and the least time the chip could take for them. The counts
do not depend on which code runs the search, so a PR that replaces a kernel
leaves every share built on them well defined.
"""
from __future__ import annotations


def least_time(peaks: dict, *, int8_ops: float, hbm_bytes: float) -> tuple:
    """(seconds, bound): the larger of the compute term (int8 operations at
    the int8 peak) and the memory term (bytes at the HBM bandwidth), and
    which of the two it is."""
    compute = int8_ops / peaks["int8_ops"]
    memory = hbm_bytes / peaks["hbm_bw"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def hamming_search(q: int, n: int, d: int) -> dict:
    """Exact Hamming top-k of ``q`` queries over ``n`` codes of ``d`` bits:
    the distance as a +/-1 int8 product (one multiply and one add per bit
    and pair) and one read of the codes and the queries."""
    return {"int8_ops": 2.0 * q * n * d, "hbm_bytes": (n + q) * d / 8.0}


def search_least_time(peaks: dict, q: int, n: int, d: int) -> float:
    return least_time(peaks, **hamming_search(q, n, d))[0]
