"""Pallas TPU kernels: the fused two-pass counting select (temporal sort).

The paper's AP engine never materializes distances: inverted-Hamming
counters race toward a threshold and nearer vectors *report earlier*, so the
sort is a counting process over the bounded domain [0, d]. These two kernels
are that pipeline on TPU — the (Q, N) distance matrix never exists in HBM:

* **pass 1** (``hamming_hist_pallas``, the "race"): stream code tiles
  HBM->VMEM, XOR+popcount against the query tile, and accumulate a
  per-query distance histogram. Only (Q, bins) counts leave the kernel —
  the same reduction the AP performs by keeping counters next to the
  Hamming macros. At large ``bins`` the race runs in two levels
  (kernels/ops.py::_race): the same kernel counts ``dist >> s`` into
  ceil(bins / 2^s) coarse lanes, then, called again with a per-query
  window base, the 2^s distances of the coarse bucket that holds r*. Each
  pair then updates about 2·sqrt(bins) counters instead of bins.
* **pass 2** (``hamming_emit_pallas``, the "reports"): re-stream the SAME
  tiles, recompute distances in VMEM (recompute is ~free; the scan is
  bandwidth-bound), and scatter the winners straight into their output
  slot: ids with dist < r* in index order first, then dist == r* ties in
  index order, where r* is the per-query k-th-smallest radius derived from
  the pass-1 histogram. Only (Q, k) ids/dists leave the kernel.

HBM traffic drops from O(Q*N*4) bytes of distances to O(Q*(bins+k)) — the
codes themselves are read twice, which for W words of codes vs N ints of
distances is a win whenever 2*W < 4*Q words, i.e. always for batched queries.

Both kernels take the valid-row count ``n_valid`` as a scalar (SMEM) so
padded dataset rows — block-alignment padding here, chunk padding in the
engine's scan — are masked exactly, by global row id, inside the kernel.

**Tile layout.** Every vector value is built from whole (8, 128) tiles,
the shape Mosaic (the TPU kernel compiler) lays out natively:

* the codes enter TRANSPOSED, (W, N): data rows run along the 128 lanes.
  A (N, W) int32 array with W <= 8 would pad every row to 128 lanes in
  VMEM (and force a 16x padded relayout of the whole datastore in HBM);
  (W, N) is the physical layout XLA already gives a narrow (N, W) array,
  so the transpose in the wrappers is free;
* a sub-tile's distances are computed as a (BQ, sub) tile (queries on
  sublanes, rows on lanes) by a static loop over the W words, then
  transposed once to (sub, BQ) so that rows run down the sublanes;
* the scatters (pass-1 histogram, pass-2 slot one-hot) then consume that
  transposed tile 8 rows (one sublane group) at a time: an (8, BQ) slice
  compared against a (lanes, 8, BQ) iota and accumulated elementwise into a
  (lanes, 8, BQ) output block (one per query block, so any BQ that is a
  multiple of 8 is a legal block). No reduction or relayout runs in the
  inner loop; the wrappers sum the 8 sublane partials and transpose back;
* pass 2's in-tile ranks (the prefix count of winners in index order) are
  one 0/1 matmul with a (sub, sub) lower-triangular matrix — exact in
  bf16 x bf16 -> f32 for sub <= 2^24;
* per-query vectors (r*, n_lt, slot_base) enter as one (1, BQ) row per
  query block.

Grid is (Q/BQ, N/BN) with the N dimension innermost; output blocks map to
the same block for every j and are revisited: initialized at j == 0,
accumulated thereafter. Running per-query emit counts for pass 2 are carried
across j in a VMEM scratch.

The grid owns the WHOLE datastore in one invocation (kernels/ops.py pads N
to a block multiple; the engine no longer chunk-scans this path), which
enables **block-min pruning**: pass 1 additionally emits a tiny
(Q/BQ, N/BN) int32 summary — the minimum valid distance in each
(query-block, data-block) tile. Pass 2 compares each tile's summary entry
against the widest winning radius max(r*) of its query block and wraps the
entire recompute+emit body in ``pl.when``: a tile that provably holds no
winner costs one SMEM scalar compare instead of a re-streamed
XOR/popcount/scatter. On clustered or sorted datastores most pass-2 tiles
skip. Skipping is exact — the emit counters only ever advance on winners,
so an all-loser tile leaves every carried count and output slot untouched.

Both kernels additionally take a per-(query-block, data-block) **enable
mask** of the same (Q/BQ, N/BN) shape (all-ones when the caller passes
none). A disabled tile is *outside the candidate set* — the index-probing
contract of core/layout.py: pass 1 skips it outright (it contributes
nothing to any histogram and summarizes to ``bins``, so every query's r* is
computed over the enabled rows only), and pass 2 composes the mask with
the block-min bound. Because r* derives from the masked histogram, skipping
disabled tiles in pass 2 is exact in the same sense as the block-min skip:
no enabled (q, x) pair is ever dropped, disabled pairs were never
candidates. The per-tile scalars (mask, summary, pass-2 run flag) live in
SMEM as one (1, 1, N/BN) row per query block, indexed by ``program_id``.

The emit pass finally takes two **sharding hooks** — the paper's counters
are additive partial histograms, so the same two kernels serve the
distributed counting select (kernels/ops.py::hamming_topk_sharded) when a
datastore spans several devices: ``slot_base`` (per-query initial value of
the carried below-r* emit counter — this shard's exclusive-scan base into
the global (Q, k) output) and ``id_base`` (a scalar added to every emitted
row id, so winners leave the kernel carrying GLOBAL ids while untouched
slots stay zero and a cross-device ``psum`` assembles the disjoint slot
ranges without any gather/sort of candidates). Both default to zero, which
is exactly the single-device behaviour.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tuning import race_lanes

# rows of the transposed distance tile one scatter step consumes: one
# sublane group, so every scatter operand is a whole number of vregs
_GROUP = 8


def _as_i32(a: jax.Array) -> jax.Array:
    return a if a.dtype == jnp.int32 else a.astype(jnp.int32)


def _codes(q_packed: jax.Array, x_packed: jax.Array):
    """Packed codes as the kernels take them: 32-bit words as stored
    (converting a uint32 datastore would copy it), queries in the codes'
    dtype, codes transposed to (W, N) — a free bitcast of XLA's layout for
    a narrow (N, W) array."""
    x = x_packed if x_packed.dtype in (jnp.int32, jnp.uint32) else (
        x_packed.astype(jnp.int32))
    return q_packed.astype(x.dtype), x.T


def _tile_dist(q, xs, bins: int):
    """(BQ, W) queries x (W, sub) transposed codes -> (BQ, sub) clamped
    distances, rows on lanes: one 2-D XOR+popcount per code word."""
    dist = None
    for w in range(q.shape[1]):
        pc = jax.lax.population_count(
            jax.lax.bitwise_xor(q[:, w:w + 1], xs[w:w + 1, :])
        ).astype(jnp.int32)
        dist = pc if dist is None else dist + pc
    return jnp.minimum(dist, bins - 1)


def _sub_tiles(x_ref, bn: int, sub: int, body, init):
    """Run ``body(start, xs, carry)`` over the (W, sub) code sub-tiles of
    the (W, bn) block. A single sub-tile is sliced statically (no dynamic
    lane offset for Mosaic to align)."""
    if bn == sub:
        return body(0, x_ref[...], init)

    def step(s, carry):
        start = pl.multiple_of(s * sub, sub)
        return body(start, x_ref[:, pl.ds(start, sub)], carry)

    return jax.lax.fori_loop(0, bn // sub, step, init)


def _scatter_groups(sub: int, fn):
    """Apply ``fn(offset)`` to each 8-row group of a (sub, BQ) tile."""
    def step(g, carry):
        fn(pl.multiple_of(g * _GROUP, _GROUP))
        return carry

    jax.lax.fori_loop(0, sub // _GROUP, step, 0)


# ---------------------------------------------------------------------------
# pass 1: fused distance + histogram (the "race")
# ---------------------------------------------------------------------------

def _hist_kernel(nv_ref, en_ref, q_ref, x_ref, *refs, bins: int, lanes: int,
                 shift: int, window: int, sub: int, bn: int):
    if window:
        base_ref, hist_ref, dt_ref = refs
        bmin_ref = None
    else:
        hist_ref, bmin_ref, dt_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    # a disabled tile is outside the candidate set: it contributes nothing
    # to the histogram and summarizes to bins, so pass 2 skips it too
    if bmin_ref is not None:
        bmin_ref[0, 0, j] = jnp.int32(bins)

    @pl.when(en_ref[0, 0, j] != 0)
    def _work():
        n_valid = nv_ref[0]
        q = q_ref[...]                                     # (BQ, W)
        bq = q.shape[0]
        bin_ids = jax.lax.broadcasted_iota(jnp.int32, (lanes, _GROUP, bq), 0)

        def count(off):
            d8 = dt_ref[pl.ds(off, _GROUP), :]             # (8, BQ)
            hist_ref[0] += (d8[None] == bin_ids).astype(jnp.int32)

        def lane_of(dist):
            """(sub, BQ) distances -> histogram lanes; invalid rows (at
            bins) go to lane ``lanes``, outside every histogram bin."""
            if window:                 # fine level: [base, base + window)
                return jnp.where(dist < bins, dist - base_ref[0], lanes)
            if shift:                  # coarse level: dist >> shift
                return jnp.where(dist < bins, dist >> shift, lanes)
            return dist

        def body(start, xs, bmin):
            dist = _tile_dist(q, xs, bins)                 # (BQ, sub)
            gid = j * bn + start + jax.lax.broadcasted_iota(
                jnp.int32, (1, sub), 1)
            # invalid (padding) rows become bins: outside every histogram
            # bin, and a fully-padded tile summarizes to bins > any r*
            dist = jnp.where(gid < n_valid, dist, bins)
            dt_ref[...] = lane_of(dist.T)                  # (sub, BQ)
            _scatter_groups(sub, count)
            return bmin if bmin_ref is None else jnp.minimum(bmin, dist)

        if bmin_ref is None:
            _sub_tiles(x_ref, bn, sub, body, jnp.int32(0))
        else:
            bmin = _sub_tiles(x_ref, bn, sub, body,
                              jnp.full((bq, sub), bins, jnp.int32))
            bmin_ref[0, 0, j] = jnp.min(bmin)


def _tile_rows(a: jax.Array, nq: int, nj: int) -> jax.Array:
    """(nq, nj) per-tile scalars -> the kernels' (nq, 1, nj) SMEM rows."""
    a = _as_i32(a)
    assert a.shape == (nq, nj), (a.shape, nq, nj)
    return a.reshape(nq, 1, nj)


def _unblock(a: jax.Array) -> jax.Array:
    """(Q/bq, lanes, 8, bq) per-group partials -> (Q, lanes)."""
    nq, lanes, _, bq = a.shape
    return jnp.sum(a, axis=2).transpose(0, 2, 1).reshape(nq * bq, lanes)


def _row_spec(nj: int):
    return pl.BlockSpec((1, 1, nj), lambda i, j: (i, 0, 0),
                        memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("bins", "shift", "window", "bq",
                                             "bn", "sub", "interpret",
                                             "return_tiles"))
def hamming_hist_pallas(q_packed: jax.Array, x_packed: jax.Array, bins: int,
                        n_valid: jax.Array | None = None,
                        block_mask: jax.Array | None = None,
                        base: jax.Array | None = None,
                        block_min: jax.Array | None = None,
                        shift: int = 0, window: int = 0,
                        bq: int = 64, bn: int = 1024, sub: int = 64,
                        interpret: bool = False, return_tiles: bool = False):
    """q: (Q, W), x: (N, W) -> (hist (Q, lanes) int32,
    block_min (Q/bq, N/bn) int32[, tiles_run int32]).

    ``hist`` is the per-query distance histogram; ``block_min`` is the
    minimum valid distance within each (query-block, data-block) grid tile
    (bins where a tile holds no valid row) — the pruning summary pass 2
    consumes. Rows with global id >= n_valid (default N) are excluded
    exactly from both outputs. ``block_mask``: (Q/bq, N/bn) int32 enable
    mask (None = all tiles enabled); a zero tile is skipped outright — its
    rows are outside the candidate set, so they are excluded from the
    histogram and its summary entry is bins. ``return_tiles=True`` also
    returns how many grid tiles the kernel ran: the sum of the enable rows
    it was handed.

    The two levels of the two-level race (``ops._race``) are the same
    kernel with one static choice each:

    * ``shift > 0`` (coarse): ``hist`` counts ``dist >> shift`` into
      ``race_lanes(bins, shift)`` lanes; ``block_min`` is as above.
    * ``window > 0`` with ``base`` (Q,) int32 (fine): ``hist`` (Q, window)
      counts ``dist - base`` and ignores every distance outside
      [base, base + window). ``block_min`` (the coarse call's summary; None
      = no pruning) then also disables every tile whose minimum lies above
      the widest window of its query block: none of its rows falls in any
      window, so skipping it is exact. No summary is made (None)."""
    Q, W = q_packed.shape
    N, _ = x_packed.shape
    bq, bn = min(bq, Q), min(bn, N)
    sub = min(sub, bn)
    assert (Q % bq == 0 and N % bn == 0 and bn % sub == 0
            and sub % _GROUP == 0), (Q, N, bq, bn, sub)
    assert (window > 0) == (base is not None) and not (window and shift)
    nq, nj = Q // bq, N // bn
    nv = jnp.full((1,), N, jnp.int32) if n_valid is None else (
        jnp.asarray(n_valid, jnp.int32).reshape(1))
    en = _tile_rows(jnp.ones((nq, nj), jnp.int32) if block_mask is None
                    else block_mask, nq, nj)
    lanes = window or race_lanes(bins, shift)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        _row_spec(nj),
        pl.BlockSpec((bq, W), lambda i, j: (i, 0)),
        pl.BlockSpec((W, bn), lambda i, j: (0, j)),
    ]
    hist_spec = pl.BlockSpec((1, lanes, _GROUP, bq), lambda i, j: (i, 0, 0, 0))
    hist_shape = jax.ShapeDtypeStruct((nq, lanes, _GROUP, bq), jnp.int32)
    args = [nv, en, *_codes(q_packed, x_packed)]
    if window:
        b1 = _as_i32(base).reshape(nq, 1, bq)
        if block_min is not None:
            top = jnp.max(b1[:, 0], axis=1, keepdims=True) + (window - 1)
            en = jnp.where(_tile_rows(block_min, nq, nj) <= top[:, None],
                           en, 0)
            args[1] = en
        in_specs.append(pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, 0)))
        args.append(b1)
        out_specs, out_shape = hist_spec, hist_shape
    else:
        out_specs = [hist_spec, _row_spec(nj)]
        out_shape = [hist_shape,
                     jax.ShapeDtypeStruct((nq, 1, nj), jnp.int32)]

    out = pl.pallas_call(
        functools.partial(_hist_kernel, bins=bins, lanes=lanes, shift=shift,
                          window=window, sub=sub, bn=bn),
        grid=(nq, nj),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((sub, bq), jnp.int32)],
        interpret=interpret,
    )(*args)
    hist8, bmin = (out, None) if window else out
    res = (_unblock(hist8), None if window else bmin.reshape(nq, nj))
    if return_tiles:
        return res + (jnp.sum(en != 0, dtype=jnp.int32),)
    return res


# ---------------------------------------------------------------------------
# pass 2: re-stream + emit winners (the "reports")
# ---------------------------------------------------------------------------

def _emit_kernel(nv_ref, ib_ref, run_ref, q_ref, x_ref, r_ref, nlt_ref,
                 sb_ref, outd_ref, outi_ref, cnt_ref, dt_ref, st_ref, *,
                 bins: int, k: int, sub: int, bn: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        outd_ref[...] = jnp.zeros_like(outd_ref)
        outi_ref[...] = jnp.zeros_like(outi_ref)
        # the carried below-r* counter starts at this shard's slot base
        # (zero single-device): emitted winners land in [base, base+n_lt_loc)
        cnt_ref[0:1, :] = sb_ref[0]
        cnt_ref[1:2, :] = jnp.zeros_like(cnt_ref[1:2, :])

    # block-min pruning composed with the enable mask (the wrapper folds
    # both into one run flag per tile): a tile outside the candidate set,
    # or whose nearest valid row is farther than the widest winning radius
    # of its query block, cannot emit — skip the re-stream entirely.
    # Skipping leaves the carried emit counts and all output slots
    # untouched, so the skip is exact.
    @pl.when(run_ref[0, 0, j] != 0)
    def _work():
        n_valid = nv_ref[0]
        id_base = ib_ref[0]
        q = q_ref[...]                                     # (BQ, W)
        r_star = r_ref[0]                                  # (1, BQ)
        n_lt_total = nlt_ref[0]                            # (1, BQ)
        bq = q.shape[0]
        slot_ids = jax.lax.broadcasted_iota(jnp.int32, (k, _GROUP, bq), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
        tri = jnp.where(col <= row, 1.0, 0.0).astype(jnp.bfloat16)

        def prefix(m):
            """Inclusive count of set rows down the sublanes (index order)."""
            m = jnp.where(m, 1.0, 0.0).astype(jnp.bfloat16)
            return jnp.dot(tri, m, preferred_element_type=jnp.float32
                           ).astype(jnp.int32)

        def body(start, xs, carry):
            cnt_lt, cnt_tie = carry                        # (1, BQ) each
            dist = _tile_dist(q, xs, bins).T               # (sub, BQ)
            first = j * bn + start
            gid = first + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            valid = gid < n_valid
            is_lt = valid & (dist < r_star)
            is_tie = valid & (dist == r_star)
            # slot of each winner: ids with dist < r* pack first (their
            # global count is < k by construction of r*), r*-ties fill the
            # remainder in index order; overflow ties land at slot k and
            # match no output slot
            c_lt, c_tie = prefix(is_lt), prefix(is_tie)
            rank_tie = n_lt_total + cnt_tie + c_tie - 1
            slot = jnp.where(is_lt, cnt_lt + c_lt - 1,
                             jnp.where(is_tie, rank_tie, k))
            st_ref[...] = jnp.minimum(slot, k)
            dt_ref[...] = dist

            def emit(off):
                hit = st_ref[pl.ds(off, _GROUP), :][None] == slot_ids
                d8 = dt_ref[pl.ds(off, _GROUP), :]
                g8 = (first + off + id_base + jax.lax.broadcasted_iota(
                    jnp.int32, (_GROUP, bq), 0))
                outd_ref[0] += jnp.where(hit, d8[None], 0)
                outi_ref[0] += jnp.where(hit, g8[None], 0)

            _scatter_groups(sub, emit)
            return (cnt_lt + c_lt[sub - 1:sub, :],
                    cnt_tie + c_tie[sub - 1:sub, :])

        cnt_lt, cnt_tie = _sub_tiles(x_ref, bn, sub, body,
                                     (cnt_ref[0:1, :], cnt_ref[1:2, :]))
        cnt_ref[0:1, :] = cnt_lt
        cnt_ref[1:2, :] = cnt_tie


@functools.partial(jax.jit, static_argnames=("bins", "k", "bq", "bn", "sub",
                                             "interpret", "return_tiles"))
def hamming_emit_pallas(q_packed: jax.Array, x_packed: jax.Array,
                        r_star: jax.Array, n_lt: jax.Array, bins: int, k: int,
                        n_valid: jax.Array | None = None,
                        block_min: jax.Array | None = None,
                        block_mask: jax.Array | None = None,
                        slot_base: jax.Array | None = None,
                        id_base: jax.Array | None = None,
                        bq: int = 64, bn: int = 1024, sub: int = 64,
                        interpret: bool = False, return_tiles: bool = False):
    """Emit the top-k winners given the pass-1 radius.

    q: (Q, W), x: (N, W); r_star/n_lt: (Q,) int32 — per-query k-th-smallest
    radius and count of rows with dist < r* (both from the pass-1 histogram).
    ``block_min``: the (Q/bq, N/bn) int32 pruning summary from
    ``hamming_hist_pallas`` — tiles whose min distance exceeds every r* in
    their query block are skipped without recomputing a single distance.
    None disables pruning (an all-zeros summary: every tile runs).
    ``block_mask``: the same enable mask pass 1 ran under (None = all
    enabled) — disabled tiles are outside the candidate set and never
    emit. The two guards compose; pass the SAME mask to both passes.

    Sharding hooks (ops.py::hamming_topk_sharded): ``slot_base`` (Q,) int32
    is the initial value of the carried below-r* counter — this shard's
    exclusive-scan base into the global slot space (None = zeros); on the
    distributed path ``n_lt`` likewise carries the shard's TIE slot base
    (global n_lt plus the tie exclusive scan) rather than the raw global
    count. ``id_base`` is a scalar added to every emitted row id (None = 0)
    so winners leave with global ids while untouched slots stay zero.

    Returns (dists (Q, k), ids (Q, k)) int32, slot-ordered (NOT distance
    sorted): slots [0, n_lt) hold dist < r* rows in index order, subsequent
    slots hold r*-ties in index order; untouched slots are 0 — the caller
    masks slots >= n_emitted and sorts (kernels/ops.py::hamming_topk).
    ``return_tiles=True`` appends how many grid tiles ran: the sum of the
    run flags the kernel was handed."""
    Q, W = q_packed.shape
    N, _ = x_packed.shape
    bq, bn = min(bq, Q), min(bn, N)
    sub = min(sub, bn)
    assert (Q % bq == 0 and N % bn == 0 and bn % sub == 0
            and sub % _GROUP == 0), (Q, N, bq, bn, sub)
    nq, nj = Q // bq, N // bn
    nv = jnp.full((1,), N, jnp.int32) if n_valid is None else (
        jnp.asarray(n_valid, jnp.int32).reshape(1))
    ib = (jnp.zeros((1,), jnp.int32) if id_base is None
          else jnp.asarray(id_base, jnp.int32).reshape(1))
    r1 = _as_i32(r_star).reshape(nq, 1, bq)
    # one run flag per tile: enabled AND (block_min <= the widest r* of the
    # query block). Padded query rows carry r* = -1 and never raise it.
    run = jnp.ones((nq, nj), jnp.bool_)
    if block_mask is not None:
        run = run & (_tile_rows(block_mask, nq, nj)[:, 0] != 0)
    if block_min is not None:
        max_r = jnp.max(r1[:, 0], axis=1, keepdims=True)
        run = run & (_tile_rows(block_min, nq, nj)[:, 0] <= max_r)
    sb1 = (jnp.zeros((nq, 1, bq), jnp.int32) if slot_base is None
           else _as_i32(slot_base).reshape(nq, 1, bq))

    vec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, 0))
    out = pl.BlockSpec((1, k, _GROUP, bq), lambda i, j: (i, 0, 0, 0))
    od8, oi8 = pl.pallas_call(
        functools.partial(_emit_kernel, bins=bins, k=k, sub=sub, bn=bn),
        grid=(nq, nj),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _row_spec(nj),
            pl.BlockSpec((bq, W), lambda i, j: (i, 0)),
            pl.BlockSpec((W, bn), lambda i, j: (0, j)),
            vec, vec, vec,
        ],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((nq, k, _GROUP, bq), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((_GROUP, bq), jnp.int32),
                        pltpu.VMEM((sub, bq), jnp.int32),
                        pltpu.VMEM((sub, bq), jnp.int32)],
        interpret=interpret,
    )(nv, ib, _tile_rows(run, nq, nj), *_codes(q_packed, x_packed), r1,
      _as_i32(n_lt).reshape(nq, 1, bq), sb1)
    if return_tiles:
        return _unblock(od8), _unblock(oi8), jnp.sum(run, dtype=jnp.int32)
    return _unblock(od8), _unblock(oi8)
