"""Layout-aware datastore: bucket-clustered physical reordering of the
packed codes, and the translation from probed index buckets to the fused
kernels' per-(query-block, data-block) enable mask.

The paper's indexing structures (§3.4) exist to *skip most of the
datastore*; PR 2's block-min pruning can only skip tiles that happen to be
provably loser-only, which on uniform data is nothing. The lever, as
TPU-KNN (Chern et al., 2022) makes explicit for TPUs and NCAM (Lee et al.,
2016) for near-data engines, is **data layout**: physically reorder the
codes so that similar codes share grid tiles. Then

* a full fused scan prunes even on uniform data — each tile now holds one
  bucket's worth of mutually-near codes, so most tiles' min distance to a
  query block clears the block-min bound;
* index traversal drives the kernels directly: a probed bucket is a
  contiguous run of rows, i.e. a run of grid tiles, i.e. a rectangle of
  ones in the enable mask — no gathered (Q, C, W) candidate tensor ever
  materializes (the retired ``index._scan_candidates`` path).

A :class:`BucketLayout` carries the reordered codes plus the permutation
and its inverse, so every search path still returns ORIGINAL ids; the
reorder is invisible to callers except for tie order (ties at equal
distance break by layout position, not original id — the same
"report-order" freedom every candidate-list scan already has).

Masking semantics (the index contract, identical to ``_scan_candidates``):
a disabled tile is simply outside the candidate set. The mask granularity
is the grid tile, so probed buckets are rounded OUTWARD to tile
boundaries — the masked candidate set is a *superset* of the probed
buckets, never a subset: recall can only improve on the gather path.
Queries within one query block share the union of their probes (one mask
row per query block); keep query batches locality-sorted for the tightest
masks. ``kernels/tuning.py::layout_blocks`` aligns the data-block size to
the bucket size so one block rarely straddles buckets.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import binary


class BucketLayout(NamedTuple):
    """Bucket-contiguous physical layout of a packed datastore (a pytree).

    ``codes[pos] == original_codes[perm[pos]]``; bucket ``b`` occupies the
    contiguous row range ``[starts[b], starts[b+1])`` of ``codes``.
    """

    codes: jax.Array        # (N, W) uint32, reordered bucket-contiguous
    perm: jax.Array         # (N,) int32: perm[pos] = original id
    inv: jax.Array          # (N,) int32: inv[original id] = pos
    starts: jax.Array       # (B+1,) int32 bucket offsets into codes

    @property
    def n(self) -> int:
        return self.codes.shape[0]

    @property
    def n_buckets(self) -> int:
        return self.starts.shape[0] - 1

    @property
    def mean_bucket_rows(self) -> int:
        return max(1, self.n // max(self.n_buckets, 1))


def invert_permutation(perm: jax.Array) -> jax.Array:
    """O(N) scatter inverse — ``inv[perm[pos]] = pos`` — instead of a
    second O(N log N) ``argsort``. One definition for every permutation in
    this module (prebuilt layouts AND the per-shard reorder on the
    distributed path), so inverse semantics cannot drift."""
    n = perm.shape[0]
    return jnp.zeros((n,), jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))


def reorder_by_assignment(codes: jax.Array, assign: jax.Array,
                          n_buckets: int) -> BucketLayout:
    """Physically cluster ``codes`` by bucket id. assign: (N,) int32 in
    [0, n_buckets). Stable: within a bucket, original id order survives."""
    assign = jnp.asarray(assign, jnp.int32)
    perm = jnp.argsort(assign, stable=True).astype(jnp.int32)
    inv = invert_permutation(perm)
    counts = jnp.bincount(assign, length=n_buckets)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)])
    return BucketLayout(codes=codes[perm], perm=perm, inv=inv, starts=starts)


def hamming_prefix_assign(codes: jax.Array, d: int, bits: int,
                          positions: jax.Array | None = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """Pure-Hamming bucketing — no float vectors required.

    Greedily picks the ``bits`` most *balanced* bit positions (empirical
    mean closest to 1/2: maximum key entropy, hence the evenest buckets an
    axis-aligned key can give) and groups codes by that LSH key: codes
    sharing the key form one of 2^bits buckets, and two codes in one bucket
    agree on all selected bits, i.e. are Hamming-near on the key subspace.
    Pass ``positions`` to reuse a previous selection (e.g. to key queries
    the same way the datastore was keyed).

    Returns (assign (N,) int32 in [0, 2^bits), positions (bits,) int32).

    Both the bit means and the key are read straight from the packed words
    (``binary.bit_means`` / ``binary.bit_key``): nothing of size (N, d)
    is built, so a datastore that fills the device can be keyed."""
    if positions is None:
        means = binary.bit_means(codes, d)
        positions = jnp.argsort(jnp.abs(means - 0.5),
                                stable=True)[:bits].astype(jnp.int32)
    return binary.bit_key(codes, positions), positions


def default_bits(n: int) -> int:
    """Heuristic key width for the Hamming fallback: ~256 rows per bucket,
    clamped to [1, 12] (4096 buckets is plenty for any mask)."""
    return max(1, min(12, int(np.log2(max(n // 256, 2)))))


def build_layout(codes: jax.Array, d: int, n_buckets: int | None = None,
                 assign: jax.Array | None = None) -> BucketLayout:
    """Build a bucket-clustered layout. With ``assign`` (e.g. k-means/IVF
    cluster ids) the reorder follows the index's own buckets (``n_buckets``
    defaults to max(assign) + 1); without, the pure-Hamming prefix fallback
    buckets by LSH key — no float vectors. Build-time (host) only; the
    build is the ``knn.layout.build`` span of a profiler trace."""
    with jax.profiler.TraceAnnotation("knn.layout.build"):
        return _build_layout(codes, d, n_buckets, assign)


def _build_layout(codes: jax.Array, d: int, n_buckets: int | None,
                  assign: jax.Array | None) -> BucketLayout:
    if assign is None:
        bits = (n_buckets - 1).bit_length() if n_buckets else (
            default_bits(codes.shape[0]))
        assign, _ = hamming_prefix_assign(codes, d, bits)
        n_buckets = 1 << bits
    else:
        hi = int(jnp.max(assign)) + 1
        n_buckets = hi if n_buckets is None else n_buckets
        # an out-of-range bucket id would fall off `starts` and its rows
        # would silently vanish from every masked probe — refuse instead
        assert hi <= n_buckets, f"assign ids reach {hi - 1} >= {n_buckets}"
        assert int(jnp.min(assign)) >= 0, "negative bucket id"
    return reorder_by_assignment(codes, assign, n_buckets)


def local_sort(codes: jax.Array, d: int, bits: int | None = None,
               n_valid: jax.Array | None = None):
    """Trace-friendly reorder for sharded shards: key by ``bits`` evenly
    spaced code bits (static positions — no data-dependent selection, so it
    runs under jit/shard_map) and stable-sort. Returns (codes_sorted, perm)
    with perm[pos] = local id. No bucket table: shards use the reorder for
    full-scan block-min pruning only, not for masked probing.

    ``n_valid``: rows at local id >= n_valid are padding (uneven shards on
    the distributed path) — their sort key is forced past every real key,
    so they stay pinned at positions [n_valid, n) and the kernels' mask-by-
    position contract (``gid < n_valid``) keeps holding after the sort."""
    n = codes.shape[0]
    bits = bits if bits is not None else default_bits(n)
    bits = max(1, min(bits, d))
    positions = jnp.arange(bits, dtype=jnp.int32) * (d // bits)
    key = binary.bit_key(codes, positions)
    if n_valid is not None:
        key = jnp.where(jnp.arange(n) < jnp.asarray(n_valid, jnp.int32),
                        key, jnp.int32(1) << 30)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    return codes[perm], perm


def to_original_ids(perm: jax.Array, ids: jax.Array) -> jax.Array:
    """Map layout positions to original ids through ``perm``; sentinel rows
    (position >= N, the engine's pad contract) pass through unchanged. The
    clamp-then-gather keeps the sentinel from indexing out of bounds."""
    n = perm.shape[0]
    return jnp.where(ids < n, perm[jnp.minimum(ids, n - 1)], ids)


def original_ids(layout: BucketLayout, dists: jax.Array, ids: jax.Array,
                 d: int) -> jax.Array:
    """Map kernel-space positions back to original ids; sentinel slots
    (dist > d or position >= N) become -1, the candidate-scan contract."""
    n = layout.n
    real = (ids < n) & (dists <= d)
    return jnp.where(real, to_original_ids(layout.perm, ids), -1)


# ---------------------------------------------------------------------------
# probed buckets -> grid enable mask
# ---------------------------------------------------------------------------

def probe_block_mask(layout: BucketLayout, probe: jax.Array, bq: int, bn: int,
                     n_qblocks: int, n_nblocks: int) -> jax.Array:
    """Translate per-query probed bucket ids into the kernels' enable mask.

    probe: (Q, P) int32 bucket ids (duplicates fine). A data block is
    enabled for a query block iff any query in the block probes a bucket
    overlapping it; bucket ranges round OUTWARD to block boundaries (the
    superset contract above). Empty buckets enable nothing. Returns
    (n_qblocks, n_nblocks) int32; rows of query padding enable nothing."""
    q = probe.shape[0]
    lo = layout.starts[probe]                              # (Q, P)
    hi = layout.starts[probe + 1]                          # exclusive
    first = lo // bn
    last = jnp.maximum(hi - 1, lo) // bn                   # inclusive
    live = (hi > lo).astype(jnp.int32)                     # empty -> no-op
    # interval scatter (+1 at first, -1 past last) + running sum instead of
    # a (Q, P, n_nblocks) broadcast: O(Q*P + Q*n_nblocks) on the hot path
    rows = jnp.arange(q)[:, None]
    inc = jnp.zeros((q, n_nblocks + 1), jnp.int32)
    inc = inc.at[rows, first].add(live).at[rows, last + 1].add(-live)
    qmask = jnp.cumsum(inc[:, :n_nblocks], axis=1) > 0     # (Q, nblk)
    qmask = jnp.pad(qmask, ((0, n_qblocks * bq - q), (0, 0)))
    return jnp.any(qmask.reshape(n_qblocks, bq, n_nblocks),
                   axis=1).astype(jnp.int32)


def position_block_mask(layout: BucketLayout, cand: jax.Array, bq: int,
                        bn: int, n_qblocks: int, n_nblocks: int) -> jax.Array:
    """Enable mask from explicit candidate ids (multi-table indexes whose
    extra tables cannot all be layout-contiguous, e.g. LSH tables 1..T-1).

    cand: (Q, C) int32 ORIGINAL ids, -1 padded. Each candidate enables the
    data block holding its reordered position — an id-level gather plus a
    scatter into the tiny mask, not the retired (Q, C, W) code gather."""
    return position_block_mask_from_inv(layout.inv, cand, bq, bn,
                                        n_qblocks, n_nblocks)


def position_block_mask_from_inv(inv: jax.Array, cand: jax.Array, bq: int,
                                 bn: int, n_qblocks: int, n_nblocks: int
                                 ) -> jax.Array:
    """The id->position mask body, keyed by a bare inverse permutation —
    the per-shard hook on the distributed path: a shard that reordered its
    slice with ``local_sort`` has only (codes, perm), so the caller builds
    ``invert_permutation(perm)`` (the O(N) scatter inverse) and maps local
    candidate ids to sorted positions without a BucketLayout."""
    q = cand.shape[0]
    pos = inv[jnp.maximum(cand, 0)]                        # (Q, C)
    blk = jnp.where(cand >= 0, pos // bn, n_nblocks)       # pad -> dropped
    qmask = jnp.zeros((q, n_nblocks), jnp.int32).at[
        jnp.arange(q)[:, None], blk].max(1, mode="drop")
    qmask = jnp.pad(qmask, ((0, n_qblocks * bq - q), (0, 0)))
    return jnp.max(qmask.reshape(n_qblocks, bq, n_nblocks), axis=1)


# ---------------------------------------------------------------------------
# the index-driven fused select
# ---------------------------------------------------------------------------

def masked_topk(layout: BucketLayout, q_packed: jax.Array, k: int, d: int,
                probe: jax.Array | None = None,
                cand_ids: jax.Array | None = None,
                bq: int | None = None, bn: int | None = None,
                sub: int | None = None, return_stats: bool = False):
    """Index-probed top-k straight through the fused kernel pair.

    Exactly one of ``probe`` ((Q, P) bucket ids) / ``cand_ids`` ((Q, C)
    original ids, -1 padded) selects the candidate set; both may be given
    (union). ``None``/``None`` degrades to an unmasked full scan (still
    layout-reordered, so block-min pruning bites).

    Returns (dists, ids[, stats]): (Q, k) ascending, ORIGINAL ids, -1 in
    sentinel slots — the same contract as ``index._scan_candidates`` over
    the rows the mask enables. ``stats`` is ``ops.hamming_topk``'s: the
    tiles the two kernels ran, counted from the enable mask and run flags
    they were handed. Block sizes default to ``tuning.layout_blocks`` (bn
    aligned to the mean bucket size)."""
    from repro.kernels import ops, tuning

    Q, W = q_packed.shape
    n = layout.n
    bins = d + 1
    lanes = max(bins, min(k, n))
    if bn is None and (probe is not None or cand_ids is not None):
        _, bn, _ = tuning.layout_blocks(Q, n, W, lanes,
                                        layout.mean_bucket_rows)
    bq, bn, sub, q_pad, n_pad = ops.topk_geometry(Q, n, W, lanes, bq, bn, sub)
    n_qblocks, n_nblocks = q_pad // bq, n_pad // bn

    mask = None
    if probe is not None:
        mask = probe_block_mask(layout, probe, bq, bn, n_qblocks, n_nblocks)
    if cand_ids is not None:
        pmask = position_block_mask(layout, cand_ids, bq, bn, n_qblocks,
                                    n_nblocks)
        mask = pmask if mask is None else jnp.maximum(mask, pmask)

    out = ops.hamming_topk(q_packed, layout.codes, k, bins,
                           block_mask=mask, bq=bq, bn=bn, sub=sub,
                           return_stats=return_stats)
    dd, ii = out[0], out[1]
    with jax.named_scope("knn.layout.map_ids"):
        ids = original_ids(layout, dd, ii, d)
    return (dd, ids, out[2]) if return_stats else (dd, ids)


def enabled_positions(layout: BucketLayout, mask_row: np.ndarray, bn: int
                      ) -> np.ndarray:
    """Host helper (tests/benchmarks): the reordered row positions a mask
    row enables, ascending — i.e. the exact candidate set, in the exact
    scan order, of every query in that query block."""
    mask_row = np.asarray(mask_row)
    pos = [np.arange(j * bn, min((j + 1) * bn, layout.n))
           for j in np.flatnonzero(mask_row)]
    return (np.concatenate(pos) if pos
            else np.zeros((0,), np.int64)).astype(np.int32)


# ---------------------------------------------------------------------------
# mutable arena: bucket regions with reserved slack (core/mutable.py)
# ---------------------------------------------------------------------------

class Arena(NamedTuple):
    """Host-side bucket arena with per-bucket spare slack for online
    inserts (the mutable face of :class:`BucketLayout`; core/mutable.py).

    Bucket ``b`` OWNS the capacity region ``[cap_starts[b],
    cap_starts[b+1])``; its first ``n_used[b]`` slots are occupied — live
    rows interleaved with tombstones (``ids == -1``) — and the rest is
    slack reserved at build time via ``slack_frac``. Appends fill slack in
    place; deletes tombstone in place (positions of surviving rows never
    move, which is what keeps the within-bucket ascending-id order — the
    invariant that makes an installed epoch bit-identical to a rebuild).
    All arrays are numpy: this is the mutation side, never what kernels
    stream — searches run against the dense epoch ``core/mutable.py``
    gathers from the live rows."""

    codes: np.ndarray       # (cap, W) uint32
    ids: np.ndarray         # (cap,) int64 external ids; -1 = dead/slack
    values: np.ndarray      # (cap,) int32 payload (e.g. next-token ids)
    cap_starts: np.ndarray  # (B+1,) int64 capacity offsets
    n_used: np.ndarray      # (B,) int64 occupied prefix per bucket
    positions: np.ndarray   # (bits,) int32 FIXED hamming-prefix key bits
    d: int                  # code bits

    @property
    def n_buckets(self) -> int:
        return self.cap_starts.shape[0] - 1

    @property
    def capacity(self) -> int:
        return int(self.cap_starts[-1])

    def live_mask(self) -> np.ndarray:
        """(cap,) bool: occupied AND not tombstoned."""
        used = np.zeros(self.capacity, bool)
        for b in range(self.n_buckets):
            s = int(self.cap_starts[b])
            used[s:s + int(self.n_used[b])] = True
        return used & (self.ids >= 0)

    @property
    def n_live(self) -> int:
        return int(np.count_nonzero(self.live_mask()))

    @property
    def n_tombstones(self) -> int:
        return int(self.n_used.sum()) - self.n_live


def hamming_key_host(codes: np.ndarray, positions: np.ndarray) -> np.ndarray:
    """Numpy mirror of :func:`hamming_prefix_assign`'s keying for FIXED
    ``positions`` — the online-insert hot path must not re-derive the key
    bits (re-derivation drifts as data drifts, and a drifted key would
    silently re-bucket existing rows). Bit ``p`` lives at word ``p // 32``,
    bit ``p % 32`` (binary.pack_bits convention)."""
    codes = np.asarray(codes, np.uint32)
    positions = np.asarray(positions, np.int64)
    bits = (codes[:, positions // 32] >> (positions % 32).astype(np.uint32))
    bits = (bits & 1).astype(np.int64)                     # (N, nbits)
    return bits @ (np.int64(1) << np.arange(positions.shape[0],
                                            dtype=np.int64))


def bucket_capacities(counts: np.ndarray, slack_frac: float,
                      min_slack: int) -> np.ndarray:
    """Per-bucket capacity = live count + reserved slack. Every bucket —
    including an empty one — gets at least ``min_slack`` spare slots, so a
    fresh arena can always absorb appends into ANY bucket before the next
    compaction rebalances."""
    counts = np.asarray(counts, np.int64)
    slack = np.maximum(np.ceil(counts * slack_frac).astype(np.int64),
                       min_slack)
    return counts + slack


def build_arena(codes: np.ndarray, d: int, *, ids: np.ndarray,
                values: Optional[np.ndarray] = None,
                n_buckets: int | None = None,
                positions: Optional[np.ndarray] = None,
                slack_frac: float = 0.5, min_slack: int = 8) -> Arena:
    """Build a slack-reserving arena from dense rows (the mutable analogue
    of :func:`build_layout`; the ``slack_frac`` knob is THE build-time
    reservation for online appends).

    ``positions=None`` derives the hamming-prefix key bits from ``codes``
    once (the same greedy balanced selection ``build_layout`` uses) and
    stores them in the arena: every later insert and every compaction keys
    by these frozen positions, so bucket assignment is a pure function of
    a row's code for the arena's whole lifetime. Rows must arrive in
    ascending external-id order (asserted): the arena's bit-identity
    contract leans on within-bucket id order."""
    codes = np.asarray(codes, np.uint32)
    ids = np.asarray(ids, np.int64)
    assert codes.ndim == 2 and ids.shape == (codes.shape[0],)
    if ids.size:
        assert np.all(np.diff(ids) > 0), "arena rows must be id-ascending"
        assert int(ids[0]) >= 0
    values = (np.zeros(ids.shape, np.int32) if values is None
              else np.asarray(values, np.int32))
    if positions is None:
        bits = (n_buckets - 1).bit_length() if n_buckets else (
            default_bits(max(codes.shape[0], 1)))
        _, pos = hamming_prefix_assign(jnp.asarray(codes), d, bits)
        positions = np.asarray(pos, np.int32)
    else:
        positions = np.asarray(positions, np.int32)
    B = 1 << positions.shape[0]
    assign = hamming_key_host(codes, positions)
    counts = np.bincount(assign, minlength=B).astype(np.int64)
    caps = bucket_capacities(counts, slack_frac, min_slack)
    cap_starts = np.zeros(B + 1, np.int64)
    np.cumsum(caps, out=cap_starts[1:])
    W = codes.shape[1]
    a_codes = np.zeros((int(cap_starts[-1]), W), np.uint32)
    a_ids = np.full(int(cap_starts[-1]), -1, np.int64)
    a_values = np.zeros(int(cap_starts[-1]), np.int32)
    # stable scatter: within a bucket, input (ascending-id) order survives
    if codes.shape[0]:
        order = np.argsort(assign, kind="stable")
        srt = assign[order]
        dense_starts = np.concatenate(
            ([0], np.cumsum(counts)))                       # (B+1,)
        rank = np.arange(order.shape[0]) - dense_starts[srt]
        slots = cap_starts[srt] + rank
        a_codes[slots] = codes[order]
        a_ids[slots] = ids[order]
        a_values[slots] = values[order]
    return Arena(codes=a_codes, ids=a_ids, values=a_values,
                 cap_starts=cap_starts, n_used=counts.copy(),
                 positions=positions, d=d)
