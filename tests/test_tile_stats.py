"""The fused kernels' tile counts (``return_stats=True``) on every plan whose
select is ``fused`` or ``fused_scan``: the unmasked scan, a prebuilt layout,
a per-call local_sort, an index-probed enable mask, the chunked scan, and
the sharded hist_merge / hist_tree selects (4 virtual devices).

Each path is checked three ways: the counts equal a brute-force count made
here from distances (``tests/_tiles.py``), asking for them leaves the
answers bit-identical, and the compiled program names its stages with the
``knn.*`` scopes."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _tiles
from repro.core import engine, layout as layout_mod, plan as plan_mod
from repro.kernels import ops, tuning

D, K, N, CHUNK = 64, 8, 7936, 2048
BINS = D + 1
W = D // 32
STAGES = {"knn.pass1", "knn.radius", "knn.pass2", "knn.finalize"}
LOCAL_PATHS = ("fused", "fused_scan", "prebuilt", "local_sort", "masked")
SHIFT = tuning.race_shift(BINS)         # the race this width gets


@pytest.fixture(scope="module")
def data():
    x, q = _tiles.clustered(0, N, D)
    shuffled = x[np.random.default_rng(1).permutation(N)]
    return x, shuffled, q


def _local_case(path, data):
    """(search(q, return_stats), expected counts, expected scopes)."""
    x_sorted, x_shuf, q = data
    qj = jnp.asarray(q)
    lanes = max(BINS, min(K, N))
    if path in ("fused", "fused_scan"):
        xj = jnp.asarray(x_sorted)
        p = plan_mod.plan_local(plan_mod.stats_of(xj, qj, D), K, select=path,
                                chunk=CHUNK)
        assert p.select.path == path and p.candidates.layout == "none"
        search = lambda qq, rs: plan_mod.execute(p, qq, codes=xj,
                                                 return_stats=rs)
        if path == "fused":
            bq, bn = ops.topk_geometry(40, N, W, lanes)[:2]
            return search, _tiles.tile_counts(q, x_sorted, K, bq, bn,
                                              shift=SHIFT), STAGES
        # one call per chunk, each over its own rows; counts add up
        bq, bn = ops.topk_geometry(40, CHUNK, W, max(BINS, K))[:2]
        want = {"blocks_total": 0, "p1_blocks_skipped": 0,
                "p1_fine_blocks_skipped": 0, "blocks_skipped": 0}
        for c in range(-(-N // CHUNK)):
            xc = np.full((CHUNK, W), 0xFFFFFFFF, np.uint32)
            rows = x_sorted[c * CHUNK:(c + 1) * CHUNK]
            xc[:rows.shape[0]] = rows
            got = _tiles.tile_counts(q, xc, K, bq, bn, n_valid=rows.shape[0],
                                     shift=SHIFT)
            want = {key: want[key] + got[key] for key in want}
        return search, want, STAGES

    xj = jnp.asarray(x_shuf)
    lay = layout_mod.build_layout(xj, D)
    bq, bn = ops.topk_geometry(40, N, W, lanes)[:2]
    if path == "prebuilt":
        eng = engine.KNNEngine(codes=xj, d=D, layout=lay)
        assert eng.query_plan(qj, K).candidates.layout == "prebuilt"
        search = lambda qq, rs: eng.search(qq, K, return_stats=rs)
        want = _tiles.tile_counts(q, np.asarray(lay.codes), K, bq, bn,
                                  shift=SHIFT)
        return search, want, STAGES | {"knn.layout.map_ids"}
    if path == "local_sort":
        p = plan_mod.plan_local(plan_mod.stats_of(xj, qj, D), K,
                                select="fused", force="layout=local_sort")
        assert p.candidates.layout == "local_sort"
        search = lambda qq, rs: plan_mod.execute(p, qq, codes=xj,
                                                 return_stats=rs)
        sorted_codes = np.asarray(layout_mod.local_sort(xj, D)[0])
        want = _tiles.tile_counts(q, sorted_codes, K, bq, bn, shift=SHIFT)
        return search, want, STAGES | {"knn.layout.map_ids"}
    # masked: two probed buckets per query become the pass-1 enable mask
    probe = np.random.default_rng(2).integers(0, lay.n_buckets, (40, 2))
    p = plan_mod.plan_index(plan_mod.stats_of(xj, qj, D, layout=lay), K,
                            kind="kmeans", nprobe=2)
    assert p.candidates.kind == "block_mask" and p.select.path == "fused"
    search = lambda qq, rs: plan_mod.execute(
        p, qq, layout=lay, probe=jnp.asarray(probe, jnp.int32),
        return_stats=rs)
    _, bn, _ = tuning.layout_blocks(40, N, W, lanes, lay.mean_bucket_rows)
    bq, bn, _, q_pad, n_pad = ops.topk_geometry(40, N, W, lanes, None, bn)
    en = _tiles.probe_enabled(np.asarray(lay.starts), probe, bq, bn,
                              q_pad // bq, n_pad // bn)
    want = _tiles.tile_counts(q, np.asarray(lay.codes), K, bq, bn, enabled=en,
                              shift=SHIFT)
    return search, want, STAGES | {"knn.layout.map_ids"}


@pytest.fixture(scope="module", params=LOCAL_PATHS)
def local(request, data):
    search, want, stages = _local_case(request.param, data)
    q = jnp.asarray(data[2])
    return {"path": request.param, "search": search, "want": want,
            "stages": stages, "plain": search(q, False),
            "stats": search(q, True), "q": q}


def test_tile_counts_match_brute_force(local):
    stats = local["stats"][2]
    got = {key: int(stats[key]) for key in local["want"]}
    assert got == local["want"], local["path"]
    # the clustered store prunes some pass-2 tiles and runs others
    assert 0 < got["blocks_skipped"] < got["blocks_total"], got


def test_return_stats_leaves_answers_bit_identical(local):
    (d0, i0), (d1, i1, _) = local["plain"], local["stats"]
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_two_level_race_leaves_answers_bit_identical(local, monkeypatch):
    """Each local fused path answers the same through a two-level race
    (forced: shift 2 at d = 64, so the clustered queries' radii fall past
    the first window) as through the one level its width gets."""
    monkeypatch.setattr(tuning, "race_shift", lambda bins: 2)
    (d0, i0), (d1, i1) = local["plain"], local["search"](local["q"], False)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_stages_are_named_in_the_compiled_program(local):
    hlo = jax.jit(lambda qq: local["search"](qq, False)).lower(
        local["q"]).compile().as_text()
    assert local["stages"] <= _tiles.scopes(hlo), _tiles.scopes(hlo)


@pytest.mark.parametrize("case", ["composite", "gather", "concat_sort"])
def test_return_stats_refused_without_fused_kernels(case, data):
    x, _, q = data
    xj, qj = jnp.asarray(x[:512]), jnp.asarray(q)
    stats = plan_mod.stats_of(xj, qj, D)
    if case == "composite":
        p = plan_mod.plan_local(stats, K, select="composite")
    elif case == "gather":
        p = plan_mod.plan_index(stats, K, kind="kdtree")
    else:
        p = plan_mod.plan_sharded(
            plan_mod.stats_of(xj, qj, D, n_shards=4), K, axes=("data",),
            merge="concat_sort")
    with pytest.raises(ValueError, match="return_stats"):
        plan_mod.execute(p, qj, codes=xj, cand=jnp.zeros((40, 4), jnp.int32),
                         return_stats=True)


SHARDED = """
import sys
sys.path.insert(0, {tests!r})
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
import _tiles
from repro.core import engine, layout as layout_mod
from repro.kernels import ops, tuning

D, K, N, S = 64, 8, 7936, 4
merge, fanout, reorder = {merge!r}, {fanout!r}, {reorder!r}
x, q = _tiles.clustered(0, N, D)
xj, qj = jnp.asarray(x), jnp.asarray(q)
mesh = Mesh(np.array(jax.devices()[:S]), ("data",))
run = lambda qq, rs: engine.search_sharded(
    xj, qq, K, D, mesh, ("data",), merge=merge, fanout=fanout,
    reorder_local=reorder, return_stats=rs)
with mesh:
    d0, i0 = run(qj, False)
    d1, i1, st = run(qj, True)
    hlo = jax.jit(lambda qq: run(qq, False)).lower(qj).compile().as_text()

# asking for the counts leaves the answer as it was
assert (np.asarray(d0) == np.asarray(d1)).all()
assert (np.asarray(i0) == np.asarray(i1)).all()

# per shard: the global r* prunes each shard's own tiles
n_loc = N // S
bq, bn = ops.topk_geometry(40, n_loc, 2, max(D + 1, K))[:2]
r_glob = _tiles.radius(_tiles.distances(q, x), np.ones((40, N), bool), K)
want = []
for s in range(S):
    xs = x[s * n_loc:(s + 1) * n_loc]
    if reorder:
        xs = np.asarray(layout_mod.local_sort(jnp.asarray(xs), D)[0])
    want.append(_tiles.tile_counts(q, xs, K, bq, bn, r_star=r_glob,
                                   shift=tuning.race_shift(D + 1)))
per = want[0]["blocks_total"]
assert st["shard_blocks_total"] == per and st["blocks_total"] == S * per
for key in ("blocks_skipped", "p1_blocks_skipped", "p1_fine_blocks_skipped"):
    shard = np.asarray(st["shard_" + key])
    assert shard.shape == (S,), shard.shape
    assert shard.tolist() == [w[key] for w in want], (key, shard, want)
    assert int(st[key]) == sum(w[key] for w in want)
assert 0 < int(st["blocks_skipped"]) < S * per, st

found = _tiles.scopes(hlo)
stages = {{"knn.pass1", "knn.radius", "knn.pass2", "knn.finalize",
          "knn.merge.hist", "knn.merge.bases", "knn.merge.out"}}
if reorder:
    stages.add("knn.layout.map_ids")
assert stages <= found, found
print("OK")
"""


@pytest.mark.parametrize("merge,fanout,reorder", [
    ("hist_merge", 0, False), ("hist_tree", 2, False),
    ("hist_merge", 0, True)], ids=["hist_merge", "hist_tree", "reorder_local"])
def test_sharded_tile_counts_per_shard(multidevice, merge, fanout, reorder):
    tests = os.path.dirname(os.path.abspath(__file__))
    out = multidevice(SHARDED.format(tests=tests, merge=merge, fanout=fanout,
                                     reorder=reorder), n_devices=4)
    assert "OK" in out


@pytest.mark.parametrize("kind", ["clustered", "uniform"])
def test_fine_level_skips_tiles_outside_every_window(kind, monkeypatch):
    """A two-level race (forced: shift 3 at d = 64) counts the tiles its
    fine call skipped: those whose block minimum lies above every window of
    their query block. Clustered codes put most tiles there; on uniform
    codes every tile holds rows near every query's radius, so (almost) none
    are."""
    monkeypatch.setattr(tuning, "race_shift", lambda bins: 3)
    if kind == "clustered":
        x, q = _tiles.clustered(4, N, D)
    else:
        rng = np.random.default_rng(4)
        x = _tiles.pack(rng.integers(0, 2, (N, D)))
        q = _tiles.pack(rng.integers(0, 2, (40, D)))
    _, _, stats = ops.hamming_topk(jnp.asarray(q), jnp.asarray(x), K, BINS,
                                   return_stats=True)
    bq, bn = ops.topk_geometry(40, N, W, max(BINS, K))[:2]
    want = _tiles.tile_counts(q, x, K, bq, bn, shift=3)
    got = {key: int(stats[key]) for key in want}
    assert got == want
    skipped, total = got["p1_fine_blocks_skipped"], got["blocks_total"]
    if kind == "clustered":
        assert 0 < skipped < total, got
    else:
        assert skipped <= total // 10, got


FINE_SHARDED = """
import sys
sys.path.insert(0, {tests!r})
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
import _tiles
from repro.core import engine
from repro.kernels import ops, tuning

D, K, N, S = 64, 8, 4096, 4
tuning.race_shift = lambda bins: 3
rng = np.random.default_rng(5)
data = {{"clustered": _tiles.clustered(5, N, D, rows_per_cluster=512),
        "uniform": (_tiles.pack(rng.integers(0, 2, (N, D))),
                    _tiles.pack(rng.integers(0, 2, (40, D))))}}
mesh = Mesh(np.array(jax.devices()[:S]), ("data",))
n_loc = N // S
bq, bn = ops.topk_geometry(40, n_loc, 2, max(D + 1, K))[:2]
for kind, (x, q) in data.items():
    with mesh:
        _, _, st = engine.search_sharded(jnp.asarray(x), jnp.asarray(q), K, D,
                                         mesh, ("data",), return_stats=True)
    r_glob = _tiles.radius(_tiles.distances(q, x), np.ones((40, N), bool), K)
    want = [_tiles.tile_counts(q, x[s * n_loc:(s + 1) * n_loc], K, bq, bn,
                               r_star=r_glob, shift=3)
            ["p1_fine_blocks_skipped"] for s in range(S)]
    shard = np.asarray(st["shard_p1_fine_blocks_skipped"]).tolist()
    assert shard == want, (kind, shard, want)
    assert int(st["p1_fine_blocks_skipped"]) == sum(want)
    per = st["shard_blocks_total"]
    if kind == "clustered":
        assert 0 < sum(want) < S * per, (kind, want)
    else:
        assert max(want) <= per // 10, (kind, want)
print("OK")
"""


def test_sharded_fine_level_counts_per_shard(multidevice):
    tests = os.path.dirname(os.path.abspath(__file__))
    out = multidevice(FINE_SHARDED.format(tests=tests), n_devices=4)
    assert "OK" in out
