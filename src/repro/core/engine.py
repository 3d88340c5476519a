"""The kNN engine: every search path is a thin plan-builder over the
QueryPlan IR (core/plan.py) — the planner resolves the stages, the
executor runs them.

Structure mirrors the paper's system:

* the materializing selects scan one *chunk* of codes per step == one AP
  board configuration; the ``lax.scan`` over chunks with an O(k) running
  merge is "partial reconfiguration" at zero swap cost (§3.3);
* ``select="fused"`` configures the WHOLE datastore at once, as the AP
  does before a race (§3.3): one two-pass Pallas invocation owns all of N
  — no scan, no merge, no per-chunk host roundtrips — with block-min
  pruning skipping pass-2 tiles that provably hold no winner
  (kernels/topk_select.py). ``chunk`` is a no-op for it (kernel tiling
  comes from kernels/tuning.py); ``select="fused_scan"`` keeps the chunked
  variant for datastores too large to address in one invocation;
* the mesh-sharded datastore == macro-level parallelism across boards;
* the exact distributed merge is the paper's counting select writ large:
  per-rank counters are ADDITIVE partial histograms, so shards psum their
  (Q, bins) counts into one global race and emit winners into disjoint
  output slots (``merge="hist_merge"``, kernels/ops.py) — no per-shard
  top-k, no concat/sort;
* the legacy merge reports only each shard's local top-k' (``k_local``)
  == statistical activation reduction (§6.3); with ``k_local == k`` it is
  exact but moves O(shards*Q*k) candidates — kept as the
  ``merge="concat_sort"`` fallback and as THE path for k_local < k.

The decision logic — how ``select="auto"`` resolves, when a layout is
streamed, when the sharded path reorders per shard — lives in
``core/plan.py`` only; the legacy ``select=`` knob survives as a forced-
plan override through the same planner (bit-identical, deprecation-nudged;
see ``QueryPlan.explain()`` for what any call will actually run).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import layout as layout_mod, plan as plan_mod

# re-exported: the distance-method enum and composite-chunk guard moved to
# the planner with the rest of the policy, but remain part of this module's
# public surface
DistanceMethod = plan_mod.DistanceMethod
_auto_chunk = plan_mod._auto_chunk


def search_chunked(codes_packed: jax.Array, q_packed: jax.Array, k: int,
                   d: int, chunk: int = plan_mod.DEFAULT_CHUNK,
                   method: str = DistanceMethod.XOR,
                   id_offset: jax.Array | int = 0,
                   select: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Search the datastore. codes: (N, W) uint32, q: (Q, W).

    ``select``: 'auto' (planner-resolved; with no layout in sight it lands
    on the composite-key fast path), or a forced path: 'counting'
    (histogram counting select), 'bisect' (scatter-free counting select),
    'fused' (single-shot two-pass Pallas counting select with block-min
    pruning; orthogonal to ``method``, which it ignores), 'fused_scan'
    (the chunk-scanned variant of 'fused', for datastores that exceed what
    one invocation should address, e.g. codes paged in from host memory).
    All paths produce bit-identical results at any chunk size; ``chunk``
    only sets the scan granularity of the materializing/'fused_scan' paths
    (see the generated decision table in DESIGN.md).
    Returns (dists (Q,k) ascending, global ids (Q,k))."""
    if select != "auto":
        plan_mod._warn_legacy("search_chunked", "select", select)
    p = plan_mod.plan_local(plan_mod.stats_of(codes_packed, q_packed, d),
                            k, select=select, method=method, chunk=chunk)
    return plan_mod.execute(p, q_packed, codes=codes_packed,
                            id_offset=id_offset)


class KNNEngine(NamedTuple):
    """Immutable engine state (a pytree — jit/shard friendly).

    ``layout``: optional bucket-clustered physical reorder of ``codes``
    (core/layout.py). Any select that RESOLVES to the fused path then
    streams the REORDERED codes — similar codes share grid tiles, so
    block-min pruning bites even on uniform data — and maps winners back
    to original ids; the materializing selects scan the original order.
    Build one with ``with_layout()``; inspect what a search will run with
    ``query_plan(...).explain_str()``.
    """

    codes: jax.Array          # (N, W) uint32 packed
    d: int                    # code bits
    layout: Optional[layout_mod.BucketLayout] = None

    @property
    def n(self) -> int:
        return self.codes.shape[0]

    @classmethod
    def from_epoch(cls, epoch, d: int) -> "KNNEngine":
        """Engine pinned to one installed epoch of a mutable store
        (core/mutable.py). The epoch's dense codes ARE the layout's codes
        (identity perm), so this engine keeps serving a complete,
        consistent snapshot no matter how the store mutates afterwards —
        grab a new engine from a newer epoch to see newer data."""
        return cls(codes=epoch.layout.codes, d=d, layout=epoch.layout)

    def with_layout(self, n_buckets: int | None = None,
                    assign: jax.Array | None = None) -> "KNNEngine":
        """Engine with a bucket-clustered layout: by explicit bucket
        ``assign`` (e.g. IVF cluster ids) or the pure-Hamming prefix
        fallback (no float vectors needed)."""
        lay = layout_mod.build_layout(self.codes, self.d,
                                      n_buckets=n_buckets, assign=assign)
        return self._replace(layout=lay)

    def query_plan(self, q_packed: jax.Array, k: int,
                   chunk: int = plan_mod.DEFAULT_CHUNK,
                   method: str = DistanceMethod.XOR, select: str = "auto",
                   force=None) -> plan_mod.QueryPlan:
        """The QueryPlan ``search`` will execute for these arguments —
        ``select`` is resolved FIRST, so an ``"auto"`` that lands on the
        fused path sees the layout (the former literal-string check lost
        it)."""
        stats = plan_mod.stats_of(self.codes, q_packed, self.d,
                                  layout=self.layout)
        return plan_mod.plan_local(stats, k, select=select, method=method,
                                   chunk=chunk, force=force)

    def search(self, q_packed: jax.Array, k: int,
               chunk: int = plan_mod.DEFAULT_CHUNK,
               method: str = DistanceMethod.XOR, select: str = "auto",
               return_stats: bool = False):
        """(dists, ids) of the plan ``query_plan`` gives; ``return_stats``
        (fused and fused_scan plans) appends the kernels' tile counts."""
        if select != "auto":
            plan_mod._warn_legacy("KNNEngine.search", "select", select)
        p = self.query_plan(q_packed, k, chunk=chunk, method=method,
                            select=select)
        return plan_mod.execute(p, q_packed, codes=self.codes,
                                layout=self.layout, return_stats=return_stats)


# ---------------------------------------------------------------------------
# distributed search (hierarchical top-k == statistical activation reduction)
# ---------------------------------------------------------------------------

def search_sharded(codes_packed: jax.Array, q_packed: jax.Array, k: int, d: int,
                   mesh: Mesh, axes: Sequence[str], k_local: Optional[int] = None,
                   chunk: int = plan_mod.DEFAULT_CHUNK,
                   method: str = DistanceMethod.XOR,
                   select: str = "auto", reorder_local: bool = False,
                   merge: Optional[str] = None, fanout: int = 0,
                   shard_n_valid=None, shard_participate=None,
                   return_stats: bool = False):
    """Datastore sharded over ``axes`` (cardinality sharding); queries
    replicated. A thin plan-builder: the planner decides the merge
    strategy, the executor runs it.

    The exact default (k_local == k) is the **distributed counting
    select** (``merge="hist_merge"``): per-shard pass-1 histograms are
    additive partial histograms of one global race, so a single ``psum``
    of the tiny (Q, bins) counts yields ONE global per-query radius r*;
    each shard then runs pass 2 over its own slice with slot bases from an
    exclusive scan of per-shard below-r*/tie counts and scatters its
    winners into disjoint slots of the global (Q, k) output via a final
    psum. No per-shard top-k materializes and nothing is concat/sorted on
    the host — cross-device traffic is O(Q·bins) counts instead of
    O(shards·Q·k) candidates, which makes ``nshards`` a throughput knob
    rather than a merge-cost tax. ``merge="concat_sort"`` forces the
    legacy hierarchical merge (each shard reports its local top-k', one
    gathered sort); k_local < k always takes it — that is the statistical
    reduction of core/hierarchy.py (inexact, bounded), k_local=None means
    k (exact).

    ``reorder_local=True`` (fused only — the planner drops it otherwise):
    each shard bucket-clusters its OWN slice by a static Hamming key before
    the scan (``layout.local_sort`` — trace-friendly, runs inside
    shard_map) and maps winners back to global ids, so block-min pruning
    bites per shard even on uniform data; it composes with either merge
    strategy. The sort is recomputed per call; amortize by building the
    layout at placement time (KNNEngine.with_layout) when the datastore is
    static.

    ``shard_n_valid``: optional (n_shards,) valid-row counts for UNEVEN
    shards padded to a common slice size (fused select only). Results are
    bit-identical to a single-device search over the concatenation of the
    valid rows, including when k exceeds one shard's valid rows.

    ``merge="hist_tree"`` (auto past 8 shards) runs the SAME counting
    select with the histogram/output psums tree-scheduled at ``fanout``
    (default from ``tuning.merge_fanout``) — bit-identical, hierarchical
    traffic. ``shard_participate``: optional (n_shards,) 0/1 liveness
    mask (hist-family merges only) — dead shards' rows are excluded
    exactly and ids renumber over the survivors, the degraded-but-exact
    answer of the shard-fault-tolerance layer.

    ``return_stats=True`` (fused select, hist-family merge) appends the
    kernels' tile counts: per shard as (n_shards,) arrays and summed
    (``plan._execute_sharded``).
    """
    if select != "auto":
        plan_mod._warn_legacy("search_sharded", "select", select)
    axes = tuple(axes)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    stats = plan_mod.stats_of(codes_packed, q_packed, d, n_shards=n_dev)
    p = plan_mod.plan_sharded(stats, k, axes=axes, k_local=k_local,
                              select=select, method=method, chunk=chunk,
                              reorder_local=reorder_local, merge=merge,
                              fanout=fanout,
                              uneven=shard_n_valid is not None)
    return plan_mod.execute(p, q_packed, codes=codes_packed, mesh=mesh,
                            shard_n_valid=shard_n_valid,
                            shard_participate=shard_participate,
                            return_stats=return_stats)


def shard_datastore(codes_packed: jax.Array, mesh: Mesh, axes: Sequence[str]):
    """Place a packed datastore sharded over the given mesh axes."""
    sharding = NamedSharding(mesh, P(tuple(axes), None))
    return jax.device_put(codes_packed, sharding)
