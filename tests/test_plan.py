"""QueryPlan IR (core/plan.py): planner/executor equivalence matrix.

Every legacy entry point is now a thin plan-builder; these tests pin that
(a) the planner-built execution is bit-identical to the legacy forced
paths on the same inputs, across (select path x layout on/off x
indexed/full-scan x sharded/local), (b) ``select="auto"`` resolves BEFORE
the layout check (the regression this PR fixes: the literal-string test
silently dropped reordering+pruning), and (c) ``explain()`` /
``force_plan`` / the generated decision table behave.
"""
import dataclasses
import json
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import RetrievalConfig
from repro.core import binary, engine, index, layout, plan, retrieval, topk

SELECTS = ("auto", "counting", "bisect", "fused", "fused_scan")


def _data(seed, n, q, d):
    rng = np.random.default_rng(seed)
    xb = jnp.asarray(rng.integers(0, 2, (n, d)), jnp.uint8)
    qb = jnp.asarray(rng.integers(0, 2, (q, d)), jnp.uint8)
    return xb, qb


def _oracle(xb, qb, k, d):
    return topk.counting_topk(binary.hamming_ref(qb, xb), k, d)


def _quiet(fn, *a, **kw):
    """Run a legacy forced-knob call without its deprecation nudge."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return fn(*a, **kw)


# ---------------------------------------------------------------------------
# the equivalence matrix: full scan, layout on/off, every select
# ---------------------------------------------------------------------------

def test_matrix_full_scan_no_layout():
    """Layout off: every select (planner-auto included) is bit-identical —
    dists AND ids (all paths break ties by index order)."""
    n, q, d, k = 1500, 6, 64, 8
    xb, qb = _data(0, n, q, d)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    rd, ri = _oracle(xb, qb, k, d)
    eng = engine.KNNEngine(codes=xp, d=d)
    for select in SELECTS:
        dd, ii = _quiet(eng.search, qp, k, chunk=257, select=select)
        assert (dd == rd).all(), select
        assert (ii == ri).all(), select
        # and the function-style entry point agrees bit-for-bit
        fd, fi = _quiet(engine.search_chunked, xp, qp, k, d, chunk=257,
                        select=select)
        assert (dd == fd).all() and (ii == fi).all(), select


def test_matrix_full_scan_with_layout():
    """Layout on: planner-auto == forced fused (both stream the reordered
    codes, bit-identical); materializing selects still scan the original
    order and stay bit-identical to their no-layout outputs; the top-k
    DISTANCE vector is layout-invariant everywhere."""
    n, q, d, k = 1500, 6, 64, 8
    xb, qb = _data(1, n, q, d)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    rd, _ = _oracle(xb, qb, k, d)
    plain = engine.KNNEngine(codes=xp, d=d)
    eng = plain.with_layout(n_buckets=8)

    ad, ai = eng.search(qp, k, chunk=257)                     # planner auto
    fd, fi = _quiet(eng.search, qp, k, chunk=257, select="fused")
    assert (ad == fd).all() and (ai == fi).all()
    assert (ad == rd).all()
    # every returned id really has its reported distance (original ids)
    ref = np.asarray(binary.hamming_ref(qb, xb))
    assert (ref[np.arange(q)[:, None], np.asarray(ai)]
            == np.asarray(ad)).all()

    for select in ("counting", "bisect", "fused_scan"):
        ld, li = _quiet(eng.search, qp, k, chunk=257, select=select)
        pd_, pi = _quiet(plain.search, qp, k, chunk=257, select=select)
        assert (ld == pd_).all() and (li == pi).all(), select
        assert (ld == rd).all(), select


def test_engine_auto_layout_regression():
    """The satellite fix: ``select="auto"`` RESOLVES first, so an auto that
    lands on the fused path sees the layout. Before, the literal-string
    check (`select == "fused"` pre-resolution) silently dropped the
    reorder+pruning; now the plan must say so explicitly."""
    n, q, d, k = 1200, 4, 64, 5
    xb, qb = _data(2, n, q, d)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    eng = engine.KNNEngine(codes=xp, d=d).with_layout(n_buckets=8)

    p = eng.query_plan(qp, k)                                 # select="auto"
    assert p.select.path == "fused"
    assert p.candidates.layout == "prebuilt"
    # without a layout, auto stays on the composite materializing path
    p0 = engine.KNNEngine(codes=xp, d=d).query_plan(qp, k)
    assert p0.select.path == "composite"
    assert p0.candidates.layout == "none"

    ad, ai = eng.search(qp, k)
    fd, fi = _quiet(eng.search, qp, k, select="fused")
    assert (ad == fd).all() and (ai == fi).all()


# ---------------------------------------------------------------------------
# indexed: masked (planner default) vs forced gather
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def clustered():
    rng = np.random.default_rng(3)
    centers = rng.normal(size=(8, 64)) * 5
    x = (centers[rng.integers(0, 8, 3000)]
         + rng.normal(size=(3000, 64))).astype(np.float32)
    bits = (x > 0).astype(np.uint8)
    codes = binary.pack_bits(jnp.asarray(bits))
    q = jnp.asarray(x[:16])
    q_codes = binary.pack_bits(jnp.asarray(bits[:16]))
    return x, codes, q, q_codes


def test_matrix_indexed_kmeans(clustered):
    x, codes, q, q_codes = clustered
    km = index.kmeans_build(jnp.asarray(x), codes, 64, 16, iters=6)
    # the planner's default (use_layout=None) must equal the forced masked
    # path bit-for-bit, and its plan must say block_mask
    p = index.kmeans_plan(km, q.shape[0], 10, nprobe=4)
    assert p.candidates.kind == "block_mask"
    assert p.probe.kind == "kmeans" and p.probe.nprobe == 4
    ad, ai = index.kmeans_search(km, q, q_codes, 10, nprobe=4)
    fd, fi = _quiet(index.kmeans_search, km, q, q_codes, 10, nprobe=4,
                    use_layout=True)
    assert (ad == fd).all() and (ai == fi).all()
    # forced gather is the legacy reference: per-slot distances can only
    # improve on the masked superset candidate set
    gd, _ = _quiet(index.kmeans_search, km, q, q_codes, 10, nprobe=4,
                   use_layout=False)
    pg = index.kmeans_plan(km, q.shape[0], 10, nprobe=4, use_layout=False)
    assert pg.candidates.kind == "gather"
    assert (jnp.asarray(ad) <= jnp.asarray(gd)).all()


def test_matrix_indexed_no_layout_falls_back(clustered):
    x, codes, q, q_codes = clustered
    km = index.kmeans_build(jnp.asarray(x), codes, 64, 16, iters=4,
                            reorder=False)
    p = index.kmeans_plan(km, q.shape[0], 10, nprobe=4)
    assert p.candidates.kind == "gather"
    dd, ids = index.kmeans_search(km, q, q_codes, 10, nprobe=4)
    assert dd.shape == (16, 10)


def test_matrix_indexed_lsh(clustered):
    x, codes, q, q_codes = clustered
    lsh = index.lsh_build(codes, 64, n_tables=4, bits_per_table=5)
    p = index.lsh_plan(lsh, q_codes.shape[0], 10)
    assert p.candidates.kind == "block_mask" and p.probe.n_tables == 4
    ad, ai = index.lsh_search(lsh, q_codes, 10)
    fd, fi = _quiet(index.lsh_search, lsh, q_codes, 10, use_layout=True)
    assert (ad == fd).all() and (ai == fi).all()


# ---------------------------------------------------------------------------
# sharded vs local (subprocess with fake devices)
# ---------------------------------------------------------------------------

def test_matrix_sharded(multidevice):
    """Sharded planner-built execution == local full scan at k_local = k
    (exact), for both the planner-auto and the forced fused select, with
    and without reorder_local — the merge stage is lossless."""
    multidevice("""
import warnings
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import binary, engine

rng = np.random.default_rng(0)
xb = jnp.asarray(rng.integers(0, 2, (1024, 64)), jnp.uint8)
qb = jnp.asarray(rng.integers(0, 2, (8, 64)), jnp.uint8)
xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
ed, ei = engine.search_chunked(xp, qp, 10, 64)
mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
with mesh, warnings.catch_warnings():
    warnings.simplefilter("ignore", DeprecationWarning)
    sd, si = engine.search_sharded(xp, qp, 10, 64, mesh, ("data",), chunk=256)
    fd, fi = engine.search_sharded(xp, qp, 10, 64, mesh, ("data",),
                                   chunk=256, select="fused")
    rd, ri = engine.search_sharded(xp, qp, 10, 64, mesh, ("data",),
                                   chunk=256, select="fused",
                                   reorder_local=True)
assert (sd == ed).all() and (si == ei).all()
assert (fd == ed).all() and (fi == ei).all()
assert (rd == ed).all()
ref = np.asarray(binary.hamming_ref(qb, xb))
got = ref[np.arange(8)[:, None], np.asarray(ri)]
assert (got == np.asarray(rd)).all()
print("OK")
""", n_devices=4)


def test_plan_sharded_stages():
    stats = plan.StoreStats(n=1 << 12, d=64, w=2, q=8, n_shards=4)
    p = plan.plan_sharded(stats, 10, axes=("data",), k_local=4,
                          select="fused", reorder_local=True)
    assert p.merge.kind == "sharded" and p.merge.k_local == 4
    assert p.merge.reorder_local and p.candidates.layout == "local_sort"
    # reorder_local is fused-only: the planner drops it elsewhere
    p2 = plan.plan_sharded(stats, 10, axes=("data",), select="counting",
                           reorder_local=True)
    assert not p2.merge.reorder_local
    assert p2.candidates.layout == "none"
    assert "ignored" in p2.reason


def test_plan_sharded_merge_strategy():
    """The merge-strategy rule: every exact sharded plan rides the
    distributed counting select (auto resolves to fused FOR the merge);
    the statistical reduction and non-fused selects keep concat_sort."""
    stats = plan.StoreStats(n=1 << 12, d=64, w=2, q=8, n_shards=4)
    p = plan.plan_sharded(stats, 10, axes=("data",))
    assert p.select.path == "fused"
    assert p.merge.strategy == "hist_merge"
    assert p.compact().endswith("merge:hist_merge")
    # k_local < k is the statistical reduction: concat_sort only
    ps = plan.plan_sharded(stats, 10, axes=("data",), k_local=4)
    assert ps.merge.strategy == "concat_sort"
    assert "@k4" in ps.compact()
    # merge=hist_merge on a statistical plan is noted-ignored, not honored
    psf = plan.plan_sharded(stats, 10, axes=("data",), k_local=4,
                            merge="hist_merge")
    assert psf.merge.strategy == "concat_sort"
    assert "ignored" in psf.reason
    # a non-fused select cannot race histograms
    pc = plan.plan_sharded(stats, 10, axes=("data",), select="counting")
    assert pc.merge.strategy == "concat_sort"
    # forcing the legacy merge keeps legacy auto-resolution (composite)
    pl = plan.plan_sharded(stats, 10, axes=("data",), merge="concat_sort")
    assert pl.merge.strategy == "concat_sort"
    assert pl.select.path == "composite"
    # uneven shards (per-shard n_valid coming) force the fused local
    # select whatever the merge — only it masks padding exactly
    pu = plan.plan_sharded(stats, 10, axes=("data",), k_local=4, uneven=True)
    assert pu.select.path == "fused"
    assert pu.merge.strategy == "concat_sort"
    assert "uneven" in pu.reason
    with pytest.raises(ValueError):
        plan.plan_sharded(stats, 10, axes=("data",), merge="bogus")


def test_force_merge_overrides():
    """force_plan merge= key: demotions are recorded, never silent."""
    stats = plan.StoreStats(n=1 << 12, d=64, w=2, q=8, n_shards=4)
    # forced non-fused select on a hist_merge plan demotes the merge
    p = plan.plan_sharded(stats, 10, axes=("data",), force="select=counting")
    assert p.select.path == "counting"
    assert p.merge.strategy == "concat_sort"
    assert "demoted" in p.reason
    # forced k_local < k likewise
    p2 = plan.plan_sharded(stats, 10, axes=("data",), force="k_local=2")
    assert p2.merge.strategy == "concat_sort" and p2.merge.k_local == 2
    assert "demoted" in p2.reason
    # forced concat_sort via the override string
    p3 = plan.plan_sharded(stats, 10, axes=("data",), force="merge=concat_sort")
    assert p3.merge.strategy == "concat_sort"
    # merge on a local plan: noted, not applied
    p4 = plan.plan_local(plan.StoreStats(n=512, d=32, w=1, q=2), 4,
                         force="merge=hist_merge")
    assert p4.merge.kind == "none"
    assert "forced merge ignored" in p4.reason
    with pytest.raises(ValueError):
        plan.plan_sharded(stats, 10, axes=("data",), force="merge=bogus")


def test_shard_hints_merge_traffic():
    """explain() reports the predicted cross-device merge traffic: the
    planner-chosen sharded plan moves O(Q*bins) histogram counts, not the
    legacy O(shards*Q*k) candidates, and both predictions are exposed."""
    from repro.kernels import tuning

    q, k, d, s = 256, 16, 128, 8
    stats = plan.StoreStats(n=1 << 17, d=d, w=4, q=q, n_shards=s,
                            backend="cpu")
    p = plan.plan_sharded(stats, k, axes=("data",))
    m = p.explain()["geometry"]["merge"]
    assert m["strategy"] == "hist_merge" and m["n_shards"] == s
    bins = d + 1
    # a two-level race psums its coarse and fine histograms, not all bins
    shift = tuning.race_shift(bins)
    assert shift and m["hist_psum_bytes"] == 4 * q * (
        tuning.race_lanes(bins, shift) + (1 << shift))
    assert m["counts_gather_bytes"] == 2 * 4 * q * s
    assert m["output_psum_bytes"] == 2 * 4 * q * k
    assert m["merge_bytes"] == m["hist_merge_bytes"]
    assert m["concat_sort_bytes"] == 2 * 4 * q * k * s
    # the headline drop: O(Q*bins) counts beat O(shards*Q*k) candidates
    assert m["merge_bytes"] < m["concat_sort_bytes"]
    # concat bytes scale with shards; hist_merge's psum payload does not
    m2 = tuning.shard_hints(q, k, bins, 2 * s, k_local=k)
    assert m2["concat_sort_bytes"] == 2 * m["concat_sort_bytes"]
    assert m2["hist_psum_bytes"] == m["hist_psum_bytes"]
    # the forced legacy plan reports its own (bigger) prediction
    pc = plan.plan_sharded(stats, k, axes=("data",), merge="concat_sort")
    mc = pc.explain()["geometry"]["merge"]
    assert mc["merge_bytes"] == mc["concat_sort_bytes"]
    assert "merge:" in pc.explain_str() or "merge" in pc.explain_str()


# ---------------------------------------------------------------------------
# retrieval: config-driven planning + force_plan overrides
# ---------------------------------------------------------------------------

def _store(rcfg, n=256, seed=4):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
    values = jnp.asarray(rng.integers(0, 64, n), jnp.int32)
    return hidden, retrieval.build_datastore(
        hidden, values, rcfg.code_bits, itq_iters=2, layout=rcfg.layout)


def test_knn_logits_routes_through_planner():
    rcfg = RetrievalConfig(enabled=True, code_bits=32, k=8, chunk_size=100)
    hidden, store = _store(rcfg)
    base = retrieval.knn_logits(store, hidden[:3], rcfg, vocab=64)
    for select in ("counting", "fused", "fused_scan"):
        got = _quiet(retrieval.knn_logits, store, hidden[:3], rcfg, vocab=64,
                     select=select)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=1e-6, atol=1e-6)
    # force_plan == the equivalent per-call forced select, bit-for-bit
    r2 = dataclasses.replace(rcfg, force_plan="select=fused")
    f = retrieval.knn_logits(store, hidden[:3], r2, vocab=64)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(base))
    assert retrieval.plan_for_store(store, r2, 3).select.path == "fused"


def test_store_layout_resolves_to_fused_prebuilt():
    """A store built with a layout makes auto resolve to fused+prebuilt
    (the knn_logits twin of the engine regression). The staged execution
    returns the unreordered scan's top-k DISTANCES bit-for-bit and maps
    every winner back to a valid original id (tie ids may legitimately
    differ by layout position — the documented report-order freedom)."""
    rcfg = RetrievalConfig(enabled=True, code_bits=32, k=8,
                           layout="hamming_prefix")
    hidden, store = _store(rcfg)
    p = retrieval.plan_for_store(store, rcfg, 3)
    assert p.select.path == "fused" and p.candidates.layout == "prebuilt"
    from repro.core import quantize
    q_codes = binary.pack_bits(quantize.itq_encode(hidden[:3], store.itq))
    dd, ii = plan.execute(p, q_codes, codes=store.codes, layout=store.layout)
    rd, _ = engine.search_chunked(store.codes, q_codes, rcfg.k, 32)
    assert (dd == rd).all()
    ref = np.asarray(binary.hamming_ref(
        binary.unpack_bits(q_codes, 32), binary.unpack_bits(store.codes, 32)))
    assert (ref[np.arange(3)[:, None], np.asarray(ii)]
            == np.asarray(dd)).all()
    # and the end-to-end mixture still finds the planted neighbor
    logp = retrieval.knn_logits(store, hidden[7:8], rcfg, vocab=64,
                                temperature=1.0)
    assert int(jnp.argmax(logp[0])) == int(store.values[7])


def test_rcfg_plan_field_forces_path():
    rcfg = RetrievalConfig(enabled=True, code_bits=32, k=8,
                           plan="fused_scan", chunk_size=64)
    hidden, store = _store(rcfg)
    p = retrieval.plan_for_store(store, rcfg, 2)
    assert p.select.path == "fused_scan" and p.select.chunk == 64


def test_force_sharded_keys_on_local_plan_noted_not_silent():
    """k_local/reorder_local are sharded-only: forcing them on a local
    plan must not pretend to apply — the drop is recorded in the reason."""
    stats = plan.StoreStats(n=512, d=32, w=1, q=2)
    p = plan.plan_local(stats, 4, force="k_local=2,reorder_local=1")
    assert p.merge.kind == "none"
    assert "k_local ignored" in p.reason
    assert "reorder_local ignored" in p.reason


def test_log_store_plan_is_the_server_startup_line():
    """The runtime server's per-store startup log (the serving-side
    explain()) — exercised here because the server module itself sits
    behind the not-yet-built dist layer."""
    import logging

    rcfg = RetrievalConfig(enabled=True, code_bits=32, k=4)
    _, store = _store(rcfg)
    logger = logging.getLogger("test_plan.server")
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        p = retrieval.log_store_plan(store, rcfg, q=4, logger=logger)
    finally:
        logger.removeHandler(handler)
    assert p.compact() == retrieval.plan_for_store(store, rcfg, 4).compact()
    assert any("active plan" in r.getMessage() and p.compact()
               in r.getMessage() for r in records)


def test_force_select_rebinds_layout_invariant():
    """A forced non-fused select on a layout engine must DROP the layout
    (only the fused select consumes one): ids stay bit-identical to the
    legacy per-call forced path, which scans the original order."""
    n, q, d, k = 900, 4, 64, 6
    xb, qb = _data(7, n, q, d)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    eng = engine.KNNEngine(codes=xp, d=d).with_layout(n_buckets=8)
    p = eng.query_plan(qp, k, force="select=counting")
    assert p.select.path == "counting"
    assert p.candidates.layout == "none"
    assert "layout dropped" in p.reason
    dd, ii = plan.execute(p, qp, codes=xp, layout=eng.layout)
    ld, li = _quiet(eng.search, qp, k, select="counting")
    assert (dd == ld).all() and (ii == li).all()
    # block_mask plans run the fused kernels by construction: a forced
    # select cannot rebind them and must say so, not silently comply
    stats = plan.StoreStats(n=512, d=32, w=1, q=2, has_layout=True,
                            mean_bucket_rows=64, n_buckets=8)
    pm = plan.plan_index(stats, 4, kind="kmeans", nprobe=2,
                         force="select=counting")
    assert pm.select.path == "fused"
    assert "ignored (block_mask runs fused)" in pm.reason


def test_parse_force_rejects_garbage():
    with pytest.raises(ValueError):
        plan.parse_force("select")
    with pytest.raises(ValueError):
        plan._apply_force(plan.plan_local(
            plan.StoreStats(n=128, d=32, w=1, q=1), 4), "select=nope")
    with pytest.raises(ValueError):
        plan._apply_force(plan.plan_local(
            plan.StoreStats(n=128, d=32, w=1, q=1), 4), "turbo=on")
    with pytest.raises(ValueError):
        plan._apply_force(plan.plan_local(
            plan.StoreStats(n=128, d=32, w=1, q=1), 4), "candidates=bogus")


def test_force_candidates_transitions():
    """Only block_mask->gather is executable from the public call sites
    (they build gather operands whenever the plan says gather); every
    other rebinding lacks operands and must be noted, not crash later."""
    idx_stats = plan.StoreStats(n=512, d=32, w=1, q=2, has_layout=True,
                                mean_bucket_rows=64, n_buckets=8)
    pg = plan.plan_index(idx_stats, 4, kind="kmeans", nprobe=2,
                         force="candidates=gather")
    assert pg.candidates.kind == "gather"
    assert pg.select.path == "counting"
    flat = plan.StoreStats(n=512, d=32, w=1, q=2)
    pf = plan.plan_local(flat, 4, force="candidates=gather")
    assert pf.candidates.kind == "full"
    assert "ignored" in pf.reason


def test_force_layout_notes_do_not_self_contradict():
    """Overriding the layout must scrub the planner's stale layout note
    (no 'streams the prebuilt BucketLayout; forced layout=none'), and on
    block_mask plans the override is recorded as ignored."""
    lay_stats = plan.StoreStats(n=512, d=32, w=1, q=2, has_layout=True,
                                mean_bucket_rows=64, n_buckets=8)
    p = plan.plan_local(lay_stats, 4, force="layout=off")
    assert p.candidates.layout == "none"
    assert "streams the prebuilt" not in p.reason
    assert "forced layout=none" in p.reason
    pm = plan.plan_index(lay_stats, 4, kind="kmeans", nprobe=2,
                         force="layout=off")
    assert pm.candidates.kind == "block_mask"
    assert "forced layout ignored" in pm.reason


def test_geometry_mirrors_executor_chunk_resolution():
    """explain() geometry must resolve a falsy chunk exactly like the
    executor (0 -> DEFAULT_CHUNK), not report an impossible 0-chunk scan."""
    stats = plan.StoreStats(n=1 << 17, d=128, w=4, q=16)
    p = plan.plan_local(stats, 8, select="counting", force="chunk=0")
    g = p.geometry()
    assert g["chunk"] == min(plan.DEFAULT_CHUNK, 1 << 17)
    assert g["n_chunks"] == (1 << 17) // g["chunk"]


# ---------------------------------------------------------------------------
# explain / compact / the generated decision table
# ---------------------------------------------------------------------------

def test_explain_is_jsonable_and_compact_is_row_safe():
    xb, qb = _data(5, 600, 4, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    eng = engine.KNNEngine(codes=xp, d=64).with_layout(n_buckets=4)
    p = eng.query_plan(qp, 5)
    e = json.loads(json.dumps(p.explain()))
    assert e["stages"]["select"]["path"] == "fused"
    assert e["stages"]["candidates"]["layout"] == "prebuilt"
    assert e["shape"] == {"n": 600, "d": 64, "w": 2, "q": 4, "k": 5}
    assert {"bq", "bn", "sub", "grid"} <= set(e["geometry"])
    assert e["compact"] == p.compact()
    # benchmark derived fields split on ';' and '=' and ',' — the compact
    # form must never collide with that grammar
    for ch in ";,=":
        assert ch not in p.compact()
    assert "QueryPlan[" in p.explain_str()


def test_decision_table_covers_rules_and_matches_design():
    table = plan.decision_table()
    for needle in ("auto->composite", "auto->fused", "block_mask",
                   "gather", "reorder_local", "forced select=fused_scan"):
        assert needle in table, needle
    # the committed DESIGN.md section must track the planner (CI's
    # plan-smoke gate, pinned here too so drift fails tier-1 first)
    import os
    design = os.path.join(os.path.dirname(__file__), "..", "DESIGN.md")
    assert plan.check_design(design) == 0


def test_legacy_knobs_deprecation_nudge():
    xb, qb = _data(6, 300, 2, 32)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    plan._WARNED.clear()
    with pytest.warns(DeprecationWarning, match="forced-plan override"):
        engine.search_chunked(xp, qp, 4, 32, select="bisect")
    # once per process per knob value: a repeat stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        engine.search_chunked(xp, qp, 4, 32, select="bisect")


# ---------------------------------------------------------------------------
# the approx tier's planner rows (kernel behavior lives in test_approx.py)
# ---------------------------------------------------------------------------

def test_matrix_approx_resolution_and_force():
    """select="approx" is planner-resolvable and force-selectable but NEVER
    an auto target; its recall knob rides the force grammar."""
    stats = plan.StoreStats(n=4096, d=64, w=2, q=8)
    path, reason = plan.resolve_select("approx", stats)
    assert path == "approx" and "forced" in reason
    # auto stays exact with and without a layout
    assert plan.resolve_select("auto", stats)[0] == "composite"
    lay_stats = dataclasses.replace(stats, has_layout=True,
                                    mean_bucket_rows=64, n_buckets=64)
    assert plan.resolve_select("auto", lay_stats)[0] == "fused"
    # force grammar: select + recall_target together
    p = plan.plan_local(stats, 5, force="select=approx,recall_target=0.9")
    assert (p.select.path, p.select.recall_target) == ("approx", 0.9)
    assert p.compact() == "probe:none|cand:full|select:approx@r0.9|merge:none"
    for ch in ";,=":                    # bench-row grammar safety
        assert ch not in p.compact()


def test_matrix_approx_engine_exact_at_full_recall():
    """Engine-level select="approx" (default recall_target=1.0) joins the
    bit-identity matrix: dists AND ids equal the oracle, layout on or off."""
    n, q, d, k = 1200, 5, 64, 7
    xb, qb = _data(7, n, q, d)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    rd, ri = _oracle(xb, qb, k, d)
    eng = engine.KNNEngine(codes=xp, d=d)
    dd, ii = _quiet(eng.search, qp, k, select="approx")
    assert (dd == rd).all() and (ii == ri).all()
    # prebuilt layout streams through the approx scan like fused
    engl = eng.with_layout(n_buckets=4)
    pl = engl.query_plan(qp, k, select="approx")
    assert pl.candidates.layout == "prebuilt"
    ld, li = _quiet(engl.search, qp, k, select="approx")
    fd, fi = _quiet(engl.search, qp, k, select="fused")
    assert (ld == fd).all() and (li == fi).all()


def test_decision_table_has_approx_rows():
    table = plan.decision_table()
    for needle in ("approx", "rt=0.9", "rt=1", "hist_merge",
                   "retrieval_off"):
        assert needle in table, needle
