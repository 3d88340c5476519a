"""Operations, bytes and least times of the cells' shapes against values
worked out by hand."""
import pytest

import _paths  # noqa: F401
from harness import peaks, work

V5E = peaks.PEAKS["TPU v5 lite"]


def test_store_batch_least_time():
    # Q = 128, N = 2^26, d = 256: 2*128*2^26*256 = 4.398e12 int8 ops at
    # 393e12/s = 11.19 ms; the codes are 2 GiB at 819e9 B/s = 2.62 ms
    w = work.hamming_search(128, 1 << 26, 256)
    assert w["int8_ops"] == 2 * 128 * 2 ** 26 * 256
    assert w["hbm_bytes"] == (2 ** 26 + 128) * 32
    t, bound = work.least_time(V5E, **w)
    assert bound == "compute"
    assert t == pytest.approx(11.19e-3, rel=1e-3)
    assert work.search_least_time(V5E, 128, 1 << 26, 256) == t
    # each chip of the x4 cell holds 2^26 of the 2^28 rows: the same
    assert work.search_least_time(V5E, 128, (1 << 28) // 4, 256) == t


def test_small_batch_is_memory_bound():
    t, bound = work.least_time(V5E, **work.hamming_search(1, 1 << 26, 256))
    assert bound == "memory"
    assert t == pytest.approx((2 ** 26 + 1) * 32 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
    assert peaks.for_kind("TPU v5 lite")["int8_ops"] == 393e12
