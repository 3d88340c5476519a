"""The two-level histogram race of the fused select's pass 1 (``ops._race``):
a coarse call counts ``dist >> s``, a fine call counts the 2^s distances of
the coarse bucket that holds r*. It must give the one-level race's r*, n_lt
and n_emit exactly, so every (dists, ids) answer stays bit-identical.

The mode is chosen from the width alone (``tuning.race_shift``); these
tests force each mode by patching that rule, at d = 64, 128 and 256, on
uniform and clustered codes and on the edges of the distance domain, then
through the sharded selects on 4 virtual devices."""
import os

import numpy as np
import pytest

import jax.numpy as jnp

import _tiles
from repro.core import topk
from repro.kernels import ops, tuning
from repro.kernels.topk_select import hamming_hist_pallas
from repro.kernels.tuning import race_lanes

WIDTHS = (64, 128, 256)
# the shift of fewest lanes at each width (``tuning.race_shift``'s choice
# wherever it races in two levels)
SHIFT = {64: 3, 128: 3, 256: 4}
CASES = ("uniform", "clustered", "duplicates", "extremes", "ragged_n_valid",
         "k_over_n_valid", "masked_query_block", "window_edge")
Q = 40                       # two query blocks on the CPU tiling (bq = 32)


def _bits(rng, n, d, p=0.5):
    return (rng.random((n, d)) < p).astype(np.uint8)


def _case(name: str, d: int):
    """(codes (N, W) uint32, queries (Q, W) uint32, k, n_valid or None,
    which query block the mask disables or None)."""
    rng = np.random.default_rng(d + len(name))
    n, k, nv, off = 1500, 16, None, None
    if name == "clustered":
        x, q = _tiles.clustered(d, n, d, rows_per_cluster=256)
        return x, q, k, nv, off
    qb = _bits(rng, Q, d)
    xb = _bits(rng, n, d)
    if name == "duplicates":                   # r* = 0: 20 copies of each
        xb[:Q * 20] = np.repeat(qb, 20, axis=0)
    elif name == "extremes":                   # distances 0 and d only
        xb = np.ones((n, d), np.uint8)
        xb[:10] = 0
        qb = np.zeros((Q, d), np.uint8)        # 10 rows at 0: r* = d
        qb[Q // 2:] = 1                        # 1490 rows at 0: r* = 0
    elif name == "ragged_n_valid":
        nv = 1100                              # not a multiple of bn
    elif name == "k_over_n_valid":
        nv, k = 20, 32
    elif name == "masked_query_block":
        off = 1
    elif name == "window_edge":
        # all-zero queries; by data block: 10 rows at the window's first
        # distance w then far rows, a block at its last (2w - 1, the
        # block's minimum exactly at the window's top), then blocks just
        # above it (2w): r* = 2w - 1 and the fine level skips those blocks
        w = 1 << SHIFT[d]
        bn = ops.topk_geometry(Q, n, d // 32, d + 1)[1]
        dist = np.full(n, 2 * w)
        dist[:bn] = d
        dist[:10] = w
        dist[bn:2 * bn] = 2 * w - 1
        xb = (np.arange(d)[None, :] < dist[:, None]).astype(np.uint8)
        qb = np.zeros((Q, d), np.uint8)
    return _tiles.pack(xb), _tiles.pack(qb), k, nv, off


def _mask(q, x, k, off):
    if off is None:
        return None
    d = x.shape[1] * 32
    bq, bn, _, q_pad, n_pad = ops.topk_geometry(q.shape[0], x.shape[0],
                                                x.shape[1], max(d + 1, k))
    return jnp.ones((q_pad // bq, n_pad // bn), jnp.int32).at[off].set(0)


def _pass1(q, x, k, nv, mask, shift):
    """(r*, n_lt, n_emit, fine-level tiles run) of pass 1 raced with the
    given shift."""
    d = x.shape[1] * 32
    bins, k_k = d + 1, min(k, x.shape[0])
    qp, xp, bq, bn, sub = ops._topk_blocked(jnp.asarray(q), jnp.asarray(x),
                                            max(bins, k_k), None, None, None)
    race = ops._race(lambda **kw: hamming_hist_pallas(
        qp, xp, bins, jnp.int32(x.shape[0] if nv is None else nv),
        block_mask=mask, bq=bq, bn=bn, sub=sub, interpret=True,
        return_tiles=True, **kw), q.shape[0], k_k, bins, shift)
    return [np.asarray(v) for v in (race.r_star, race.n_lt, race.n_emit)] + [
        race.fine_tiles]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("d", WIDTHS)
def test_two_level_pass1_equals_one_level(d, case):
    x, q, k, nv, off = _case(case, d)
    mask = _mask(q, x, k, off)
    one = _pass1(q, x, k, nv, mask, 0)
    two = _pass1(q, x, k, nv, mask, SHIFT[d])
    for name, a, b in zip(("r_star", "n_lt", "n_emit"), one, two):
        np.testing.assert_array_equal(a, b, err_msg=name)
    if case == "duplicates":
        assert (one[0] == 0).all()
    if case == "extremes":
        assert (one[0][:Q // 2] == d).all() and (one[0][Q // 2:] == 0).all()
    if case == "window_edge":
        w = 1 << SHIFT[d]
        assert (one[0] == 2 * w - 1).all()
        bq, _, _, q_pad, _ = ops.topk_geometry(Q, x.shape[0], x.shape[1],
                                               d + 1)
        # the fine level runs the first two blocks of each query block only
        assert int(two[3]) == 2 * (q_pad // bq)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("d", WIDTHS)
def test_two_level_topk_matches_counting(d, case, monkeypatch):
    x, q, k, nv, off = _case(case, d)
    mask = _mask(q, x, k, off)
    N, bins = x.shape[0], d + 1
    dist = _tiles.distances(q, x)
    cand = np.ones_like(dist, bool)
    if nv is not None:
        cand[:, nv:] = False
    if off is not None:
        bq = ops.topk_geometry(Q, N, x.shape[1], max(bins, k))[0]
        cand[off * bq:(off + 1) * bq] = False
    rd, ri = topk.counting_topk(jnp.asarray(np.where(cand, dist, bins)),
                                k, bins)
    ri = jnp.where(rd >= bins, N, ri)
    rd = jnp.minimum(rd, bins)
    out = {}
    for shift in (0, SHIFT[d]):
        monkeypatch.setattr(tuning, "race_shift", lambda b, s=shift: s)
        out[shift] = ops.hamming_topk(jnp.asarray(q), jnp.asarray(x), k, bins,
                                      n_valid=nv, block_mask=mask)
    for fd, fi in out.values():
        np.testing.assert_array_equal(np.asarray(fd), np.asarray(rd))
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(ri))


def _fake_hist_call(hist):
    """``hamming_hist_pallas`` over a given full (Q, bins) histogram, in
    plain jax.numpy: the coarse call sums buckets of 2^shift distances, the
    fine call reads the window [base, base + window)."""
    bins = hist.shape[1]

    def call(shift=0, base=None, block_min=None, window=0):
        if window:
            idx = base[:, None] + jnp.arange(window)
            inside = (idx >= 0) & (idx < bins)
            fine = jnp.take_along_axis(hist, jnp.clip(idx, 0, bins - 1), -1)
            return jnp.where(inside, fine, 0), None
        lanes = race_lanes(bins, shift)
        coarse = jnp.zeros((hist.shape[0], lanes), jnp.int32).at[
            :, jnp.arange(bins) >> shift].add(hist)
        return coarse, jnp.zeros((1, 1), jnp.int32)
    return call


@pytest.mark.parametrize("bins", [9, 65, 129, 257])
def test_two_level_radius_equals_radius_from_cum(bins):
    """Pure jax.numpy: on synthetic histograms (sparse, dense, empty rows,
    totals below k, everything in the top bin) every shift's two-level
    derivation gives ``_radius_from_cum``'s r*, n_lt and n_emit, and the
    counts below and at r*."""
    rng = np.random.default_rng(bins)
    rows = [rng.poisson(rng.uniform(0.01, 3.0), bins) for _ in range(48)]
    rows += [np.zeros(bins, int), np.eye(bins, dtype=int)[-1] * 40,
             np.eye(bins, dtype=int)[0] * 3, np.ones(bins, int)]
    hist = jnp.asarray(np.stack(rows), jnp.int32)
    for k_k in (1, 7, 16, 300):
        _, r1, lt1, em1 = ops._radius_from_cum(jnp.cumsum(hist, -1), k_k)
        below1, tie1 = ops._split_at(hist, r1)
        for shift in range(1, (bins - 1).bit_length()):
            race = ops._race(_fake_hist_call(hist), hist.shape[0], k_k, bins,
                             shift)
            for a, b in ((r1, race.r_star), (lt1, race.n_lt),
                         (em1, race.n_emit), (below1, race.lt),
                         (tie1, race.tie)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=f"k={k_k} s={shift}")


def test_rule_picks_levels_from_the_width():
    """One level up to d = 64, two from d = 96 on; the coarse and fine
    lanes together are the fewest the shift can give, far under bins."""
    assert tuning.race_shift(9) == 0 and tuning.race_shift(65) == 0
    for bins in (97, 129, 257):
        s = tuning.race_shift(bins)
        assert s == SHIFT.get(bins - 1, s) > 0
        lanes = race_lanes(bins, s) + (1 << s)
        assert lanes == min(race_lanes(bins, t) + (1 << t)
                            for t in range(1, 9))
        assert lanes < bins // 3


SHARDED = """
import sys
sys.path.insert(0, {tests!r})
import warnings
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
import _tiles
from repro.core import engine
from repro.kernels import ops, tuning

d, K, S = {d!r}, 16, 4
mode = {mode!r}
rng = np.random.default_rng(3)
# clustered: each query's winners sit on one shard and the fine level
# prunes; uniform: winners on every shard, so every slot base counts
data = [_tiles.clustered(3, 2048, d, rows_per_cluster=256),
        (_tiles.pack(rng.integers(0, 2, (1024, d))),
         _tiles.pack(rng.integers(0, 2, (40, d))))]
mesh = Mesh(np.array(jax.devices()[:S]), ("data",))
kw = {{"hist_merge": {{}}, "hist_tree": {{"merge": "hist_tree", "fanout": 2}},
      "participate": {{"shard_participate": jnp.asarray([1, 0, 1, 1])}},
      "local_sort": {{"reorder_local": True}}}}[mode]
for x, q in data:
    N = x.shape[0]
    xj, qj = jnp.asarray(x), jnp.asarray(q)
    out = {{}}
    for shift in (0, {shift!r}):
        tuning.race_shift = lambda bins, s=shift: s
        with mesh, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out[shift] = engine.search_sharded(xj, qj, K, d, mesh,
                                               ("data",), **kw)
    (d0, i0), (d1, i1) = out[0], out[{shift!r}]
    assert (np.asarray(d0) == np.asarray(d1)).all()
    assert (np.asarray(i0) == np.asarray(i1)).all()
    # and both equal the single-device select over the searched rows (ids
    # renumbered over the survivors under participate, as a rebuilt store
    # would; under local_sort ties at r* follow the layout's order)
    rows = x
    if mode == "participate":
        rows = np.concatenate([x[:N // 4], x[N // 2:]])
    tuning.race_shift = lambda bins: 0
    rd, ri = ops.hamming_topk(qj, jnp.asarray(rows), K, d + 1)
    assert (np.asarray(d1) == np.asarray(rd)).all()
    if mode != "local_sort":
        assert (np.asarray(i1) == np.asarray(ri)).all()
print("OK")
"""


@pytest.mark.parametrize("mode,d", [
    ("hist_merge", 64), ("hist_merge", 256), ("hist_tree", 64),
    ("participate", 64), ("local_sort", 64)])
def test_sharded_two_level_bit_identical(multidevice, mode, d):
    tests = os.path.dirname(os.path.abspath(__file__))
    out = multidevice(SHARDED.format(tests=tests, d=d, mode=mode,
                                     shift=SHIFT[d]), n_devices=4)
    assert "OK" in out
