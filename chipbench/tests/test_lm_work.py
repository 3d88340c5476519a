"""Operations, bytes and least times of the RWKV-6 Finch 1.6B serving work
(``harness/lm_work.py``) against values worked out by hand."""
import json
import os

import pytest

import _paths
from harness import lm_work, peaks

V5E = peaks.PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def finch():
    with open(os.path.join(_paths.BENCH, "configs",
                           "knnlm-rwkv6-1.6b.json")) as f:
        return json.load(f)


def test_block_weights_by_hand(finch):
    # time mix: 5 x 2048^2 + 2048*160 + 5*32*2048 + 2 * 2048*64;
    # channel mix: 2 x 2048*7168 + 2048^2
    blk = lm_work.rwkv6_block_weights(finch)
    assert blk["matrix"] == (5 * 2048 ** 2 + 2 * 2048 * 160 + 2 * 2048 * 64
                             + 2 * 2048 * 7168 + 2048 ** 2)
    assert blk["vector"] == 16 * 2048
    # with the embedding and the head, the whole model: 1,599,873,024
    total = (24 * (blk["matrix"] + blk["vector"]) + 2 * 65536 * 2048
             + 4 * 2048)
    assert total == 1599873024


def test_decode_step_is_memory_bound(finch):
    w = lm_work.rwkv6_decode(finch, 16)
    matrix = 24 * lm_work.rwkv6_block_weights(finch)["matrix"] + 65536 * 2048
    assert w["flops"] == 2 * matrix * 16
    # per row: 24 layers x (32 heads x 64 x 64 x 4 B + 2 x 2048 x 2 B),
    # read and written
    state = 24 * (32 * 64 * 64 * 4 + 2 * 2048 * 2)
    assert w["hbm_bytes"] == 2 * matrix + 4 * (24 * 16 + 4) * 2048 \
        + 2 * 16 * state
    t = lm_work.least_time(V5E, **w)
    assert t == pytest.approx(w["hbm_bytes"] / 819e9)
    assert t == pytest.approx(4.080e-3, rel=1e-3)


def test_prefill_is_compute_bound_and_window_sums(finch):
    blocks = 24 * lm_work.rwkv6_block_weights(finch)["matrix"]
    w = lm_work.rwkv6_prefill(finch, 4608, 16)
    assert w["flops"] == 2 * (blocks * 4608 + 65536 * 2048 * 16)
    assert lm_work.least_time(V5E, **w) == pytest.approx(w["flops"] / 197e12)
    got = lm_work.lm_least_time(V5E, finch, decode_steps=10, rows=16,
                                prefill_tokens=4608, prefill_prompts=16)
    assert got == pytest.approx(
        10 * lm_work.least_time(V5E, **lm_work.rwkv6_decode(finch, 16))
        + w["flops"] / 197e12)
