"""Fused two-pass Pallas top-k (hamming_topk + engine select="fused"):
equivalence with the oracle and the materialized-distance paths, including
the padding/masking edges the kernels handle internally; the single-shot
contract (pass 1 and one emit pallas_call over the whole datastore, no
scan, no merge) and block-min pruning on clustered datastores."""
import numpy as np

import jax.numpy as jnp
import pytest

from repro.core import binary, engine, topk
from repro.kernels import ops, ref, tuning

# shapes chosen to hit: N/Q multiples of the default blocks, N NOT a
# multiple of any block (pad masking), W from 1 to 8 words, Q below one
# sublane tile
SHAPES = [(8, 1024, 64), (5, 999, 96), (16, 300, 32), (1, 4097, 256),
          (33, 130, 160)]


def _data(seed, n, q, d):
    rng = np.random.default_rng(seed)
    xb = jnp.asarray(rng.integers(0, 2, (n, d)), jnp.uint8)
    qb = jnp.asarray(rng.integers(0, 2, (q, d)), jnp.uint8)
    return xb, qb


@pytest.mark.parametrize("q,n,d", SHAPES)
@pytest.mark.parametrize("k", [1, 10, 64])
def test_hamming_topk_matches_oracle(q, n, d, k):
    xb, qb = _data(0, n, q, d)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    dist = binary.hamming_ref(qb, xb)
    rd, _ = topk.topk_ref(dist, min(k, n))
    cd, ci = topk.counting_topk(dist, k, d)
    fd, fi = ops.hamming_topk(qp, xp, k, d + 1)
    assert (fd[:, :min(k, n)] == rd).all()          # distances == sorted oracle
    assert (fd == cd).all() and (fi == ci).all()    # bit-identical tie semantics


def test_heavy_ties_at_r_star():
    """d=8 over 4096 rows: hundreds of ties at every radius; the emit pass
    must fill the tie slots in index order exactly like counting_topk."""
    xb, qb = _data(1, 4096, 4, 8)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    dist = binary.hamming_ref(qb, xb)
    for k in (3, 50, 512):
        cd, ci = topk.counting_topk(dist, k, 8)
        fd, fi = ops.hamming_topk(qp, xp, k, 9)
        assert (fd == cd).all() and (fi == ci).all()


def test_k_exceeds_rows():
    """k > N: real rows first, then (bins, N) padding, same as counting."""
    xb, qb = _data(2, 37, 3, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    dist = binary.hamming_ref(qb, xb)
    cd, ci = topk.counting_topk(dist, 50, 64)
    fd, fi = ops.hamming_topk(qp, xp, 50, 65)
    assert (fd == cd).all() and (fi == ci).all()
    assert (fd[:, 37:] == 65).all() and (fi[:, 37:] == 37).all()


def test_n_valid_masks_tail_rows():
    """Rows >= n_valid must be invisible to both passes (the engine's
    chunk-padding contract)."""
    xb, qb = _data(3, 512, 4, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    nv = 300
    dist = binary.hamming_ref(qb, xb[:nv])
    cd, ci = topk.counting_topk(dist, 16, 64)
    fd, fi = ops.hamming_topk(qp, xp, 16, 65, n_valid=nv)
    assert (fd == cd).all() and (fi == ci).all()


@pytest.mark.parametrize("q,n,d", SHAPES)
def test_hamming_hist_pad_path(q, n, d):
    """Direct test of ops.hamming_hist pad handling: block-alignment rows
    added by the wrapper must contribute nothing, even when their (zero)
    codes would land in bin 0 and silently corrupt r*. The ragged SHAPES
    force padding; the aligned ones cover the no-pad path."""
    xb, qb = _data(4, n, q, d)
    xp = binary.pack_bits(xb).astype(jnp.int32)
    qp = binary.pack_bits(qb).astype(jnp.int32)
    hist = ops.hamming_hist(qp, xp, d + 1)
    expect = ref.hamming_hist_ref(qp, xp, d + 1)
    assert (hist == expect).all()
    assert int(hist.sum()) == q * n


def test_hamming_hist_clamp_bin():
    """Distances >= bins must clamp into the top bin, matching the ref."""
    qp = jnp.zeros((2, 2), jnp.int32)
    xp = jnp.full((70, 2), -1, jnp.int32)          # distance 64 everywhere
    hist = ops.hamming_hist(qp, xp, 5)
    assert (hist[:, 4] == 70).all() and int(hist.sum()) == 2 * 70


@pytest.mark.parametrize("n,q,d,k,chunk", [
    (500, 6, 64, 10, 130),      # ragged chunks: last chunk mostly padding
    (2048, 16, 128, 16, 512),   # aligned chunks
    (300, 4, 32, 400, 128),     # k > N through the scan merge
    (17, 2, 32, 5, 16),         # tiny: N barely above one chunk
])
def test_engine_fused_bit_identical(n, q, d, k, chunk):
    xb, qb = _data(5, n, q, d)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    ad, ai = engine.search_chunked(xp, qp, k, d, chunk=chunk, select="auto")
    fd, fi = engine.search_chunked(xp, qp, k, d, chunk=chunk, select="fused")
    assert (ad == fd).all() and (ai == fi).all()


def test_single_shot_one_hist_one_emit(monkeypatch):
    """select='fused' on N >> chunk must issue one pass-1 call per level
    of the race its width gets (``tuning.race_shift``) and one emit
    pallas_call — no lax.scan over chunks, no merge_topk — and stay
    bit-identical to counting_topk."""
    from repro.kernels import ops as ops_mod

    calls = {"hist": 0, "emit": 0}
    real_hist, real_emit = ops_mod.hamming_hist_pallas, ops_mod.hamming_emit_pallas
    monkeypatch.setattr(ops_mod, "hamming_hist_pallas",
                        lambda *a, **kw: (calls.__setitem__("hist", calls["hist"] + 1),
                                          real_hist(*a, **kw))[1])
    monkeypatch.setattr(ops_mod, "hamming_emit_pallas",
                        lambda *a, **kw: (calls.__setitem__("emit", calls["emit"] + 1),
                                          real_emit(*a, **kw))[1])

    def no_merge(*a, **kw):
        raise AssertionError("merge_topk must not run on the fused path")

    monkeypatch.setattr(topk, "merge_topk", no_merge)
    xb, qb = _data(7, 3000, 4, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    fd, fi = engine.search_chunked(xp, qp, 8, 64, chunk=256, select="fused")
    levels = 2 if tuning.race_shift(64 + 1) else 1
    assert calls == {"hist": levels, "emit": 1}
    cd, ci = topk.counting_topk(binary.hamming_ref(qb, xb), 8, 64)
    assert (fd == cd).all() and (fi == ci).all()


def test_fused_scan_matches_single_shot():
    """The retained chunk-scanned variant stays bit-identical to the
    single-shot path (and hence to every other select)."""
    xb, qb = _data(11, 700, 4, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    fd, fi = engine.search_chunked(xp, qp, 9, 64, chunk=128, select="fused")
    sd, si = engine.search_chunked(xp, qp, 9, 64, chunk=128,
                                   select="fused_scan")
    assert (fd == sd).all() and (fi == si).all()


def test_clustered_prunes_most_blocks():
    """Clustered/sorted datastore: one near cluster owns the top-k, so the
    block-min guard must skip most pass-2 blocks — and results stay
    bit-identical to counting_topk."""
    rng = np.random.default_rng(8)
    d, n, k = 128, 4096, 10
    near = (rng.random((64, d)) < 0.05).astype(np.uint8)
    far = (rng.random((n - 64, d)) < 0.9).astype(np.uint8)
    xb = jnp.asarray(np.concatenate([near, far]), jnp.uint8)
    qb = jnp.zeros((4, d), jnp.uint8)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    fd, fi, stats = ops.hamming_topk(qp, xp, k, d + 1, return_stats=True)
    cd, ci = topk.counting_topk(binary.hamming_ref(qb, xb), k, d)
    assert (fd == cd).all() and (fi == ci).all()
    frac = float(stats["blocks_skipped"]) / stats["blocks_total"]
    assert frac >= 0.5, f"pruned only {frac:.2f} of {stats['blocks_total']}"


def test_uniform_data_prunes_nothing_and_stays_exact():
    """Uniform random data: nothing is provably loser-only, so the guard
    must pass (almost) every block through — exactness is the contract."""
    xb, qb = _data(12, 1024, 8, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    fd, fi, stats = ops.hamming_topk(qp, xp, 16, 65, return_stats=True)
    cd, ci = topk.counting_topk(binary.hamming_ref(qb, xb), 16, 64)
    assert (fd == cd).all() and (fi == ci).all()
    # every block of diverse uniform data holds some near row for some
    # query: the guard must not skip a single tile (no over-pruning)
    assert int(stats["blocks_skipped"]) == 0
    assert int(stats["p1_blocks_skipped"]) == 0


def test_block_mask_restricts_candidate_set():
    """An explicit enable mask must make the result the exact top-k over the
    enabled blocks only — candidate-set semantics, not post-filtering: r*
    derives from the masked histogram, so a query seeing < k rows emits
    sentinels rather than stealing rows from disabled blocks."""
    xb, qb = _data(13, 1024, 8, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    bq, bn, sub, q_pad, n_pad = ops.topk_geometry(8, 1024, 2, 65, bn=256)
    nblk = n_pad // bn
    assert nblk == 4
    # enable blocks 1 and 3 -> rows [256, 512) u [768, 1024)
    mask = jnp.asarray([[0, 1, 0, 1]], jnp.int32)
    md, mi = ops.hamming_topk(qp, xp, 10, 65, block_mask=mask,
                              bq=bq, bn=bn, sub=sub)
    rows = np.r_[256:512, 768:1024]
    dist = binary.hamming_ref(qb, xb[rows])
    rd, ri = topk.counting_topk(dist, 10, 64)
    ri = jnp.asarray(rows, jnp.int32)[ri]       # candidate slot -> global id
    assert (md == rd).all() and (mi == ri).all()


def test_block_mask_below_k_candidates_sentinels():
    """Mask leaves fewer than k rows: live slots are the full enabled set,
    the rest are (bins, N) sentinels — same contract as n_valid < k."""
    xb, qb = _data(14, 1024, 4, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    bq, bn, sub, q_pad, n_pad = ops.topk_geometry(4, 1024, 2, 65, bn=256)
    mask = jnp.zeros((q_pad // bq, n_pad // bn), jnp.int32).at[:, 2].set(1)
    k = 300                                     # > 256 enabled rows
    md, mi = ops.hamming_topk(qp, xp, k, 65, block_mask=mask,
                              bq=bq, bn=bn, sub=sub)
    dist = binary.hamming_ref(qb, xb[512:768])
    rd, ri = topk.counting_topk(dist, k, 64)
    assert (md[:, :256] == rd[:, :256]).all()
    assert (mi[:, :256] == ri[:, :256] + 512).all()
    assert (md[:, 256:] == 65).all() and (mi[:, 256:] == 1024).all()


def test_block_mask_stats_report_both_passes():
    xb, qb = _data(15, 2048, 8, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    bq, bn, sub, q_pad, n_pad = ops.topk_geometry(8, 2048, 2, 65, bn=256)
    nblk = n_pad // bn
    mask = jnp.ones((q_pad // bq, nblk), jnp.int32).at[:, :nblk // 2].set(0)
    _, _, stats = ops.hamming_topk(qp, xp, 8, 65, block_mask=mask,
                                   bq=bq, bn=bn, sub=sub, return_stats=True)
    assert int(stats["p1_blocks_skipped"]) == nblk // 2
    # pass 2 composes the mask with block-min: at least the masked tiles
    assert int(stats["blocks_skipped"]) >= nblk // 2
    assert stats["blocks_total"] == nblk


def test_k_exceeds_n_valid():
    """k > n_valid < N: live slots match counting_topk over the valid
    prefix; the rest are (bins, N) sentinels."""
    xb, qb = _data(9, 256, 3, 64)
    xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
    nv, k = 20, 32
    cd, ci = topk.counting_topk(binary.hamming_ref(qb, xb[:nv]), k, 64)
    fd, fi = ops.hamming_topk(qp, xp, k, 65, n_valid=nv)
    assert (fd[:, :nv] == cd[:, :nv]).all() and (fi[:, :nv] == ci[:, :nv]).all()
    assert (fd[:, nv:] == 65).all() and (fi[:, nv:] == 256).all()


def test_engine_class_select_knob():
    xb, qb = _data(6, 400, 3, 64)
    eng = engine.KNNEngine(codes=binary.pack_bits(xb), d=64)
    ad, ai = eng.search(binary.pack_bits(qb), k=7)
    fd, fi = eng.search(binary.pack_bits(qb), k=7, select="fused")
    assert (ad == fd).all() and (ai == fi).all()


def test_sharded_fused_bit_identical(multidevice):
    """search_sharded(select='fused') under shard_map on 4 fake devices —
    the traced n_valid scalar and the SMEM BlockSpec must survive SPMD."""
    multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import binary, engine

rng = np.random.default_rng(0)
xb = jnp.asarray(rng.integers(0, 2, (1024, 64)), jnp.uint8)
qb = jnp.asarray(rng.integers(0, 2, (8, 64)), jnp.uint8)
xp, qp = binary.pack_bits(xb), binary.pack_bits(qb)
mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
with mesh:
    ad, ai = engine.search_sharded(xp, qp, 10, 64, mesh, ("data",), chunk=256)
    fd, fi = engine.search_sharded(xp, qp, 10, 64, mesh, ("data",), chunk=256,
                                   select="fused")
assert (ad == fd).all() and (ai == fi).all()
print("OK")
""", n_devices=4)


def test_topk_blocks_divisibility():
    """The heuristic must return kernel-legal shapes: bq | Q_pad, sub | bn,
    sublane/lane alignment."""
    for (Q, N, W, lanes) in [(1, 100, 1, 9), (256, 1 << 17, 8, 257),
                             (64, 4096, 4, 129), (7, 50, 2, 33)]:
        bq, bn, sub = tuning.topk_blocks(Q, N, W, lanes, backend="cpu")
        assert bq % 8 == 0 and bn % sub == 0 and sub % 8 == 0
        bq_t, bn_t, sub_t = tuning.topk_blocks(Q, N, W, lanes, backend="tpu")
        assert bn_t % sub_t == 0 and sub_t % 128 == 0 and bq_t % 8 == 0
        # (lanes, 8, bq) one-hot, bq padded to 128 lanes, and the (W, bn)
        # code tile, W padded to 8 sublanes, respect their VMEM budgets
        assert 4 * lanes * 8 * (-(-bq_t // 128) * 128) <= (2 << 20)
        assert 4 * 8 * bn_t <= (4 << 20)
