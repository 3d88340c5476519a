"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend kernels execute with ``interpret=True`` — the kernel
body runs faithfully in Python/XLA for correctness validation; on TPU the
same calls compile to Mosaic (and nothing interprets). Shapes are padded to block multiples here so
the kernels stay assert-simple; padded dataset rows are masked exactly
inside the kernels by the ``n_valid`` scalar. Block shapes come from the
shared heuristic in kernels/tuning.py unless explicitly overridden.

``hamming_topk`` is the engine's single-shot fused select: pass 1 (one
hist ``pallas_call``, or two for the two-level race of ``_race``) and one
emit ``pallas_call`` over the WHOLE datastore for any N, with the pass-1
block-min summary pruning pass-2 tiles that cannot hold a winner.

``hamming_topk_sharded`` is the same two-pass select distributed across a
device mesh (call it INSIDE ``shard_map``): the paper's counters are
additive partial histograms, so a ``psum`` of the tiny per-level counts
yields ONE global per-query radius r*, and each shard then emits its
winners into disjoint slots of the global (Q, k) output — no per-shard
top-k materialization, no host concat/sort merge.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ref, tuning
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.hamming import hamming_distance_pallas
from repro.kernels.topk_select import hamming_emit_pallas, hamming_hist_pallas


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_rows(a: jax.Array, target: int, fill: int = 0) -> jax.Array:
    pad = target - a.shape[0]
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)
    return a


def hamming_distance(q_packed: jax.Array, x_packed: jax.Array,
                     bq: int | None = None,
                     bn: int | None = None) -> jax.Array:
    """(Q, W) x (N, W) packed -> (Q, N) int32 (Pallas on TPU, interpreted on
    CPU). Arbitrary Q/N; padding handled here."""
    Q, W = q_packed.shape
    N = x_packed.shape[0]
    hbq, hbn = tuning.distance_blocks(Q, N, W)
    bq, bn = bq or hbq, bn or hbn
    qp = _pad_rows(q_packed, _round_up(Q, bq))
    xp = _pad_rows(x_packed, _round_up(N, bn))
    out = hamming_distance_pallas(qp, xp, bq=bq, bn=bn, interpret=_interpret())
    return out[:Q, :N]


def topk_geometry(Q: int, N: int, W: int, lanes: int,
                  bq: int | None = None, bn: int | None = None,
                  sub: int | None = None, backend: str | None = None):
    """The padded grid geometry ``hamming_topk`` will run under:
    (bq, bn, sub, q_pad, n_pad). ``lanes = max(bins, min(k, N))``.

    Exposed so layout-aware callers (core/layout.py) can build a
    (q_pad//bq, n_pad//bn) block mask that tiles EXACTLY like the kernels —
    any drift between this and the internal prologue is a shape error, not
    a silent mis-mask. ``backend`` pins the heuristic to a named backend
    (planner/table introspection); None uses the runtime default."""
    hbq, hbn, hsub = tuning.topk_blocks(Q, N, W, lanes, backend=backend)
    bq, bn, sub = bq or hbq, bn or hbn, sub or hsub
    sub = min(sub, bn)
    return bq, bn, sub, _round_up(Q, bq), _round_up(N, bn)


def _topk_blocked(q_packed: jax.Array, x_packed: jax.Array, lanes: int,
                  bq: int | None, bn: int | None, sub: int | None):
    """Shared pad-to-blocks prologue for the two-pass kernels."""
    Q, W = q_packed.shape
    N = x_packed.shape[0]
    bq, bn, sub, q_pad, n_pad = topk_geometry(Q, N, W, lanes, bq, bn, sub)
    # 32-bit codes stay as stored: converting a uint32 datastore would copy
    # it (the kernels read either)
    qp = _pad_rows(q_packed, q_pad)
    xp = _pad_rows(x_packed, n_pad)
    return qp, xp, bq, bn, sub


def hamming_hist(q_packed: jax.Array, x_packed: jax.Array, bins: int,
                 n_valid: jax.Array | int | None = None,
                 bq: int | None = None, bn: int | None = None,
                 sub: int | None = None) -> jax.Array:
    """Fused distance+histogram: (Q, W) x (N, W) -> (Q, bins) int32.

    Pass 1 of the two-pass counting select. Rows with global id >= n_valid
    (default: all N rows valid) — including the block-alignment padding added
    here — are masked exactly inside the kernel. (The kernel's second
    output, the block-min pruning summary, is an implementation detail of
    ``hamming_topk`` and is dropped here.)"""
    Q, N = q_packed.shape[0], x_packed.shape[0]
    qp, xp, bq, bn, sub = _topk_blocked(q_packed, x_packed, bins, bq, bn, sub)
    nv = jnp.asarray(N if n_valid is None else n_valid, jnp.int32)
    hist, _ = hamming_hist_pallas(qp, xp, bins, nv, bq=bq, bn=bn, sub=sub,
                                  interpret=_interpret())
    return hist[:Q]


def _gather(c: jax.Array, i: jax.Array) -> jax.Array:
    return jnp.take_along_axis(c, i[:, None], axis=-1)[:, 0]


def _radius_from_cum(cum: jax.Array, k_k, below=0):
    """The counting select's "finish line": from a cumulative histogram,
    the per-query effective k, k-th-smallest radius r*, strict-below count
    and emit count. ONE definition — the single-device and distributed
    selects, and both levels of the two-level race (``_race``), must derive
    the radius identically or they diverge.

    ``cum`` may cover a window of consecutive distances instead of all of
    them: it then counts from ``below``, the candidates under the window,
    r* comes back as an offset into the window, and ``k_k`` is the race's
    own (Q,) k_eff, which the window's last count already reaches."""
    k_eff = jnp.minimum(k_k, cum[:, -1])                             # (Q,)
    r_star = jnp.argmax(cum >= k_eff[:, None], axis=-1).astype(jnp.int32)
    n_lt = jnp.where(r_star > 0, _gather(cum, jnp.maximum(r_star - 1, 0)),
                     below)
    n_emit = jnp.minimum(_gather(cum, r_star), k_eff)
    return k_eff, r_star, n_lt, n_emit


def _split_at(hist: jax.Array, r: jax.Array, below=0):
    """A histogram's counts strictly below and at per-query lane ``r``,
    counting from ``below`` (the candidates under its first lane)."""
    cum = jnp.cumsum(hist, axis=-1) + jnp.expand_dims(below, -1)
    lt = jnp.where(r > 0, _gather(cum, jnp.maximum(r - 1, 0)), below)
    return lt, _gather(hist, r)


class _Race(NamedTuple):
    """What pass 1 hands pass 2: per query the race's r*, strict-below
    count n_lt and emit count n_emit (global, after ``merge``), this
    device's own counts below and at r* (the sharded select's slot bases),
    the block-min summary, and the tiles each pass-1 call ran (the fine
    call's None on a one-level race; ``return_tiles`` only)."""
    r_star: jax.Array
    n_lt: jax.Array
    n_emit: jax.Array
    lt: jax.Array
    tie: jax.Array
    block_min: jax.Array
    tiles: jax.Array | None
    fine_tiles: jax.Array | None


def _race(hist_call, Q: int, k_k: int, bins: int, shift: int,
          merge=None) -> _Race:
    """Pass 1 and the radius, for the single-device and sharded selects.

    ``hist_call(**kw)`` runs ``hamming_hist_pallas`` over this device's
    padded rows and query batch with one level's keywords; ``merge`` sums
    partial histograms across devices (None: one device). ``shift`` is
    ``tuning.race_shift(bins)``:

    * 0 — one level: the (Q, bins) histogram gives r* directly.
    * s > 0 — two levels, exact and never a full histogram: the coarse
      call counts ``dist >> s`` (ceil(bins / 2^s) lanes) and emits the
      block-min summary; its finish line is the coarse bucket c* holding
      r*, and the count below c* is exact. The fine call counts the 2^s
      distances of that bucket, from base = c* << s, skipping tiles whose
      block minimum lies above every window of their query block; the
      finish line over the window, counted from the coarse count below
      it, is r*. r*, n_lt and n_emit equal the one-level race's."""
    merge = merge or (lambda h: h)
    with jax.named_scope("knn.pass1"):
        p1 = hist_call(shift=shift)
    hist, block_min = p1[0][:Q], p1[1]
    q_pad = p1[0].shape[0] - Q
    tiles = p1[2] if len(p1) > 2 else None
    with jax.named_scope("knn.merge.hist"):
        glob = merge(hist)
    with jax.named_scope("knn.radius"):
        # per-query candidate count: n_valid when unmasked, the enabled-row
        # count under a block mask — k_eff must follow it or candidates
        # with dist > 0 would be dropped whenever a query sees fewer than k
        k_eff, r_star, n_lt, n_emit = _radius_from_cum(
            jnp.cumsum(glob, axis=-1), k_k)
        lt, tie = _split_at(hist, r_star)
    if not shift:
        return _Race(r_star, n_lt, n_emit, lt, tie, block_min, tiles, None)

    width = 1 << shift
    with jax.named_scope("knn.radius"):
        base = r_star << shift
        # padded query rows get a window below every distance: they count
        # nothing and never widen their block's pruning bound
        base_p = jnp.pad(base, (0, q_pad), constant_values=-width)
    with jax.named_scope("knn.pass1"):
        p1 = hist_call(base=base_p, block_min=block_min, window=width)
    fine = p1[0][:Q]
    with jax.named_scope("knn.merge.hist"):
        glob = merge(fine)
    with jax.named_scope("knn.radius"):
        _, f, n_lt, n_emit = _radius_from_cum(
            n_lt[:, None] + jnp.cumsum(glob, axis=-1), k_eff, n_lt)
        lt, tie = _split_at(fine, f, lt)
    return _Race(base + f, n_lt, n_emit, lt, tie, block_min, tiles,
                p1[2] if len(p1) > 2 else None)


def _tree_psum(x: jax.Array, axes, fanout: int) -> jax.Array:
    """Hierarchical all-reduce: a plain psum over the trailing (intra-host)
    axes, then rounds of ``fanout``-wide grouped psums over the leading
    axis. Integer addition is associative and commutative, so the result
    is bit-identical to ``jax.lax.psum(x, axes)`` — the tree only changes
    WHICH partial sums materialize: O(log_f S) rounds of f-wide group
    reductions instead of one S-wide reduction, the inter-host half of the
    hist_tree merge strategy.

    Round structure over the leading axis (size S): at stride s (starting
    1), indices {b + off + j*s : j < f} form one group — f representatives
    of f consecutive already-reduced spans — and exchange via f-1 rotation
    ``ppermute``s so after the round every index holds the sum of its span
    of s*f consecutive elements. Rounds run while s*f divides S; a final
    group round over the surviving S//s spans closes any
    non-power-of-``fanout`` remainder. (Rotation ppermutes rather than
    ``psum(axis_index_groups=...)`` because shard_map supports the
    former; the sums are identical either way.)"""
    axes = tuple(axes)
    if len(axes) > 1:
        x = jax.lax.psum(x, axes[1:])
    a = axes[0]
    size = jax.lax.psum(1, a)          # static: python int, the axis size

    def group_round(x, s, f):
        y = x
        for r in range(1, f):
            perm = [(b + off + j * s, b + off + ((j + r) % f) * s)
                    for b in range(0, size, s * f)
                    for off in range(s) for j in range(f)]
            y = y + jax.lax.ppermute(x, a, perm)
        return y

    s = 1
    while s * fanout <= size and size % (s * fanout) == 0:
        x = group_round(x, s, fanout)
        s *= fanout
    if s < size:
        x = group_round(x, s, size // s)
    return x


def _finalize_slots(out_d: jax.Array, out_i: jax.Array, n_emit: jax.Array,
                    k: int, k_k: int, bins: int, sentinel_id):
    """Slot-ordered emit output -> the select contract: untouched slots
    become (bins, sentinel_id), one O(k log k) sort per row orders the
    winners (stable: ties keep slot order), columns beyond k_k pad with
    the same sentinels. Shared by the local and distributed epilogues."""
    Q = out_d.shape[0]
    live = jnp.arange(k_k, dtype=jnp.int32)[None, :] < n_emit[:, None]
    out_d = jnp.where(live, out_d, bins)
    out_i = jnp.where(live, out_i, sentinel_id)
    out_d, out_i = jax.lax.sort_key_val(out_d, out_i, dimension=-1)
    if k_k < k:
        out_d = jnp.concatenate(
            [out_d, jnp.full((Q, k - k_k), bins, jnp.int32)], axis=1)
        out_i = jnp.concatenate(
            [out_i, jnp.broadcast_to(jnp.asarray(sentinel_id, jnp.int32),
                                     (Q, k - k_k))], axis=1)
    return out_d, out_i


def tile_stats(blocks_total: int, p1_run, p2_run, fine_run=None) -> dict:
    """The pruning telemetry of one two-pass call, from the tile counts the
    kernel wrappers return (``return_tiles``): ``blocks_total`` (python
    int, grid tiles per pass), ``p1_blocks_skipped`` (traced int32, tiles
    pass 1 did not run: the enable mask excluded them),
    ``p1_fine_blocks_skipped`` (traced int32, tiles the fine level of a
    two-level race did not run: disabled, or whose block minimum lies above
    every window of their query block; 0 on a one-level race, which has no
    fine level) and ``blocks_skipped`` (traced int32, tiles pass 2 did not
    run: disabled, or whose block minimum exceeds every r* of their query
    block; padding-only tiles included, they always prune)."""
    total = jnp.int32(blocks_total)
    return {"blocks_total": blocks_total,
            "blocks_skipped": total - p2_run,
            "p1_blocks_skipped": total - p1_run,
            "p1_fine_blocks_skipped": (jnp.int32(0) if fine_run is None
                                       else total - fine_run)}


def hamming_topk(q_packed: jax.Array, x_packed: jax.Array, k: int, bins: int,
                 n_valid: jax.Array | int | None = None,
                 block_mask: jax.Array | None = None,
                 bq: int | None = None, bn: int | None = None,
                 sub: int | None = None, return_stats: bool = False):
    """Single-shot fused two-pass top-k over the WHOLE datastore:
    (Q, W) x (N, W) -> (dists (Q, k), ids (Q, k)).

    The engine's high-throughput select, pass 1 and one emit
    ``pallas_call`` for any N (the Pallas grid streams the N dimension;
    arbitrary N is padded to a block multiple here and masked exactly
    in-kernel): pass 1 races distances in [0, bins) (clamped at bins-1;
    pass bins > max distance for exactness) to the radius r* — one
    histogram call, or at large ``bins`` a coarse and a fine one
    (``_race``, ``tuning.race_shift``) — and emits the (Q/bq, N/bn)
    block-min pruning summary, pass 2 re-streams the codes and emits the
    winners, skipping every (query-block, data-block) tile whose summary
    proves it holds no winner. Only (Q, bins) counts or fewer, the tiny
    summary, and (Q, k) ever leave the kernels — the (Q, N) distance
    matrix is never materialized. Semantics match ``topk.counting_topk``
    on the clamped distances: ascending, ties broken by index order, rows
    beyond min(k, n_valid) padded with (bins, N). Rows with global id >=
    n_valid are excluded exactly.

    ``block_mask``: optional (q_pad//bq, n_pad//bn) int32 enable mask over
    the grid tiles (geometry from ``topk_geometry``): a zero tile is
    outside the candidate set — pass 1 skips it outright and every query's
    top-k is taken over the enabled rows only, the index-probing contract
    of core/layout.py. Queries whose candidate count falls below k get
    (bins, N) sentinels in the surplus slots, exactly like n_valid < k.

    ``return_stats=True`` additionally returns the ``tile_stats`` dict,
    counted from the flags the two kernels were handed: pass 1's enable
    rows and pass 2's run flags, summed inside their wrappers.
    """
    Q, N = q_packed.shape[0], x_packed.shape[0]
    k_k = min(k, N)
    if k_k == 0:
        out = (jnp.full((Q, k), bins, jnp.int32),
               jnp.full((Q, k), N, jnp.int32))
        if return_stats:
            return out + (tile_stats(0, jnp.int32(0), jnp.int32(0)),)
        return out
    qp, xp, bq, bn, sub = _topk_blocked(q_packed, x_packed,
                                        max(bins, k_k), bq, bn, sub)
    nv = jnp.asarray(N if n_valid is None else n_valid, jnp.int32)
    interp = _interpret()

    # pass 1: the race -> per-query radius r*, the counts below it, and the
    # block-min summary pass 2 prunes with
    race = _race(lambda **kw: hamming_hist_pallas(
        qp, xp, bins, nv, block_mask=block_mask, bq=bq, bn=bn, sub=sub,
        interpret=interp, return_tiles=return_stats, **kw),
        Q, k_k, bins, tuning.race_shift(bins))
    with jax.named_scope("knn.radius"):
        # padded query rows get r*=-1 so they emit nothing
        q_pad = qp.shape[0] - Q
        r_p = jnp.pad(race.r_star, (0, q_pad), constant_values=-1)
        nlt_p = jnp.pad(race.n_lt, (0, q_pad))

    # pass 2: the reports
    with jax.named_scope("knn.pass2"):
        p2 = hamming_emit_pallas(qp, xp, r_p, nlt_p, bins, k_k, nv,
                                 block_min=race.block_min,
                                 block_mask=block_mask, bq=bq, bn=bn,
                                 sub=sub, interpret=interp,
                                 return_tiles=return_stats)

    # untouched slots -> (bins, N) sentinels, then one O(k log k) sort per row
    with jax.named_scope("knn.finalize"):
        out_d, out_i = _finalize_slots(p2[0][:Q], p2[1][:Q], race.n_emit, k,
                                       k_k, bins, N)
    if return_stats:
        return out_d, out_i, tile_stats(int(race.block_min.size),
                                        race.tiles, p2[2], race.fine_tiles)
    return out_d, out_i


def hamming_topk_sharded(q_packed: jax.Array, x_local: jax.Array, k: int,
                         bins: int, axis_names, *, n_shards: int,
                         n_valid: jax.Array | None = None,
                         id_base: jax.Array | None = None,
                         n_total: jax.Array | int | None = None,
                         perm: jax.Array | None = None,
                         block_mask: jax.Array | None = None,
                         participate: jax.Array | None = None,
                         tree_fanout: int = 0,
                         bq: int | None = None, bn: int | None = None,
                         sub: int | None = None, return_stats: bool = False):
    """Distributed counting select — the sharded fused top-k WITHOUT a
    concat/sort merge. Call INSIDE ``shard_map``; collectives run over
    ``axis_names`` (``n_shards`` = product of their sizes).

    q: (Q, W) replicated; x_local: (n_loc, W), this shard's slice. The
    result (dists (Q, k), ids (Q, k)) is replicated and bit-identical to
    ``hamming_topk`` over the concatenation of every shard's valid rows
    (under ``perm`` the DISTANCES keep that guarantee but ties at the r*
    cut are picked in layout-position order — the same report-order
    freedom every layout-streaming path has, core/layout.py):

    1. each shard runs pass 1 over its slice — its histogram is a
       PARTIAL histogram of the global race (counters are additive);
    2. a ``psum`` merges them; the global r*, below-count n_lt and emit
       count derive exactly as in the single-device select (``_race``: on
       a two-level race the coarse histograms merge into one global
       window base, each shard counts that window, and a second psum of
       the (Q, 2^s) fine counts gives r*);
    3. each shard derives its own below-r*/tie counts from its LOCAL
       histograms; one tiny (Q, 2)-per-shard all-gather turns them into
       exclusive-scan slot bases, so every shard owns a disjoint slice of
       the global (Q, k) slot space (without ``perm``, ids stay in global
       index order — shard slices are contiguous id ranges — so tie
       semantics match the single-device kernel bit-for-bit, including
       the first-(k - n_lt) global tie cut; with ``perm``, in-shard tie
       order follows layout positions instead);
    4. each shard runs pass 2 locally (block-min pruning and the enable
       mask compose as usual) with ``slot_base``/``id_base`` from step 3,
       and a final ``psum`` assembles the disjoint slots.

    Cross-device traffic is O(Q·bins) histogram counts (fewer on a
    two-level race) + O(Q·n_shards) base counts + the O(Q·k) output —
    never O(n_shards·Q·k) candidates.

    ``n_valid``: this shard's valid-row count (rows beyond it are padding;
    uneven shards pad to a common n_loc). ``id_base``/``n_total``: this
    shard's exclusive prefix of valid rows and the global valid total —
    derived via a scalar all-gather when None (even shards need neither:
    they default to shard_index * n_loc and n_shards * n_loc). ``perm``:
    (n_loc,) local layout permutation (``layout.local_sort``) — winners
    are emitted as layout positions and mapped back to local ids on this
    shard's owned slots before the output psum. ``block_mask``: this
    shard's (Q_pad/bq, n_loc_pad/bn) enable mask (core/layout.py
    semantics; r* then derives from the globally-merged MASKED histogram).

    ``participate``: optional (n_shards,) replicated 0/1 mask in flat-shard
    order — the fault-tolerance hook. A shard with participate == 0 (dead)
    contributes NO rows: its n_valid is zeroed, and id bases / n_total
    derive from the exclusive scan of the MASKED per-shard counts, so ids
    renumber exactly as a store rebuilt from only the surviving shards'
    rows. The result is therefore bit-identical (dists AND ids, including
    tie cuts and the all-dead n_total == 0 edge) to ``hamming_topk`` over
    that surviving-rows store. Do not combine with explicit ``id_base`` /
    ``n_total`` unless they already account for the mask.

    ``tree_fanout``: 0 (default) reduces histograms and outputs with one
    flat psum (strategy "hist_merge"); >= 2 switches both to the
    hierarchical ``_tree_psum`` schedule (strategy "hist_tree") —
    bit-identical results, tree-shaped traffic.

    ``return_stats=True`` appends THIS shard's ``tile_stats`` (its own
    kernels' tile counts; no collective): pass 2's tiles here are pruned
    against the global r*.
    """
    axes = tuple(axis_names)
    Q, W = q_packed.shape
    n_loc = x_local.shape[0]
    k_k = min(k, n_shards * n_loc)
    if k_k == 0:
        out = (jnp.full((Q, k), bins, jnp.int32),
               jnp.full((Q, k), 0, jnp.int32))
        if return_stats:
            return out + (tile_stats(0, jnp.int32(0), jnp.int32(0)),)
        return out

    # flat shard index over the collective axes (row-major, like the mesh)
    flat = jnp.zeros((), jnp.int32)
    for a in axes:
        flat = flat * jax.lax.psum(jnp.int32(1), a) + jax.lax.axis_index(a)

    part = None
    if participate is not None:
        part = jnp.asarray(participate, jnp.int32).reshape(n_shards)
    if n_valid is None:
        if part is None:
            nv = jnp.int32(n_loc)
            ib = ((flat * n_loc).astype(jnp.int32)
                  if id_base is None else id_base)
            nt = n_shards * n_loc if n_total is None else n_total
        else:
            # participation is replicated, so the masked per-shard counts —
            # and their exclusive scan — need no gather at all
            nv_all = part * jnp.int32(n_loc)                   # (n_shards,)
            nv = nv_all[flat]
            csum = jnp.cumsum(nv_all)
            ib = csum[flat] - nv_all[flat] if id_base is None else id_base
            nt = csum[-1] if n_total is None else n_total
    else:
        nv = jnp.asarray(n_valid, jnp.int32).reshape(())
        if part is not None:
            nv = nv * part[flat]
        ib, nt = id_base, n_total
        if ib is None or nt is None:
            nv_all = jax.lax.all_gather(nv, axes, tiled=False)
            nv_all = nv_all.reshape(n_shards)
            csum = jnp.cumsum(nv_all)
            ib = csum[flat] - nv_all[flat] if ib is None else ib
            nt = csum[-1] if nt is None else nt
    ib = jnp.asarray(ib, jnp.int32)
    nt = jnp.asarray(nt, jnp.int32)
    psum = ((lambda v: _tree_psum(v, axes, tree_fanout))
            if tree_fanout >= 2 else (lambda v: jax.lax.psum(v, axes)))

    qp, xp, bq, bn, sub = _topk_blocked(q_packed, x_local,
                                        max(bins, k_k), bq, bn, sub)
    interp = _interpret()

    # pass 1 locally, then merge the partial histograms: ONE global race;
    # this shard's below-r*/tie counts come from its LOCAL histograms
    race = _race(lambda **kw: hamming_hist_pallas(
        qp, xp, bins, nv, block_mask=block_mask, bq=bq, bn=bn, sub=sub,
        interpret=interp, return_tiles=return_stats, **kw),
        Q, k_k, bins, tuning.race_shift(bins), merge=psum)
    r_star, n_lt, l_lt, l_tie = race.r_star, race.n_lt, race.lt, race.tie
    with jax.named_scope("knn.radius"):
        counts = jnp.stack([l_lt, l_tie], axis=-1)                   # (Q, 2)
    # exclusive scan over the shard order = global-index-order slot bases
    with jax.named_scope("knn.merge.bases"):
        g_counts = jax.lax.all_gather(counts, axes, tiled=False)
        g_counts = g_counts.reshape(n_shards, Q, 2)
        before = (jnp.arange(n_shards, dtype=jnp.int32) < flat)[:, None]
        base_lt = jnp.sum(jnp.where(before, g_counts[:, :, 0], 0), axis=0)
        base_tie = n_lt + jnp.sum(jnp.where(before, g_counts[:, :, 1], 0),
                                  axis=0)

    # pass 2 locally: this shard's winners scatter straight into its
    # disjoint global slots (padded query rows carry r* = -1: no emission)
    with jax.named_scope("knn.radius"):
        q_pad = qp.shape[0] - Q
        r_p = jnp.pad(r_star, (0, q_pad), constant_values=-1)
        sb_p = jnp.pad(base_lt, (0, q_pad))
        tb_p = jnp.pad(base_tie, (0, q_pad))
    with jax.named_scope("knn.pass2"):
        p2 = hamming_emit_pallas(qp, xp, r_p, tb_p, bins, k_k, nv,
                                 block_min=race.block_min,
                                 block_mask=block_mask,
                                 slot_base=sb_p,
                                 id_base=None if perm is not None else ib,
                                 bq=bq, bn=bn, sub=sub, interpret=interp,
                                 return_tiles=return_stats)
    od, oi = p2[0][:Q], p2[1][:Q]
    if perm is not None:
        # winners were emitted as layout positions: map them back to local
        # ids on the slots THIS shard owns, zero elsewhere, so the psum
        # below still assembles disjoint ranges
        with jax.named_scope("knn.layout.map_ids"):
            iota = jnp.arange(k_k, dtype=jnp.int32)[None, :]
            owned = (((iota >= base_lt[:, None])
                      & (iota < (base_lt + l_lt)[:, None]))
                     | ((iota >= base_tie[:, None])
                        & (iota < (base_tie + l_tie)[:, None])))
            perm = jnp.asarray(perm, jnp.int32)
            mapped = perm[jnp.minimum(oi, n_loc - 1)] + ib
            oi = jnp.where(owned, mapped, 0)
            od = jnp.where(owned, od, 0)

    with jax.named_scope("knn.merge.out"):
        od = psum(od)
        oi = psum(oi)

    # untouched slots -> (bins, n_total) sentinels, one O(k log k) sort
    with jax.named_scope("knn.finalize"):
        out = _finalize_slots(od, oi, race.n_emit, k, k_k, bins, nt)
    if return_stats:
        return out + (tile_stats(int(race.block_min.size), race.tiles,
                                 p2[2], race.fine_tiles),)
    return out


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    bq: int = 512, bk: int = 512) -> jax.Array:
    """Causal flash-attention forward. q: (B, S, H, hd); k, v: (B, S, KV, hd)
    -> (B, S, H, hd). Pads S to a block multiple (future positions are
    causally invisible); transposes to the kernel's (B, H, S, hd) layout."""
    B, S, H, hd = q.shape
    blk = max(bq, bk)
    s_pad = _round_up(S, blk)
    if s_pad != S:
        pz = lambda a: jnp.pad(a, ((0, 0), (0, s_pad - S), (0, 0), (0, 0)))
        q, k, v = pz(q), pz(k), pz(v)
    out = flash_attention_fwd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), bq=min(bq, s_pad), bk=min(bk, s_pad),
        interpret=_interpret())
    return out.transpose(0, 2, 1, 3)[:, :S]


__all__ = ["flash_attention", "hamming_distance", "hamming_hist",
           "hamming_topk", "hamming_topk_sharded", "ref", "tile_stats",
           "topk_geometry", "tuning"]
