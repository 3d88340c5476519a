"""Block-shape heuristics + the measured autotune cache (see DESIGN.md).

One table instead of per-call-site hardcoded defaults: both passes of the
fused top-k (``hamming_hist_pallas`` / ``hamming_emit_pallas``), the
approximate partial-reduce select (``kernels/approx_select.py``) and the
materializing distance kernel ask here for their block shapes given the
problem shape and backend.

Resolution order is **measured beats default**: every lookup first consults
the :class:`AutotuneCache` — a small JSON-on-disk store of per-(backend,
kind, geometry-bucket) timings written by :func:`measure` — and only falls
back to the static heuristics below when no measurement exists. The static
heuristics ARE the seeded defaults: with an empty cache every shape is a
pure function of the inputs, so tests and CI stay deterministic (nothing
here ever times code implicitly; ``measure`` runs only when a caller
explicitly invokes it, and accepts an injectable timer so even the
measuring path is testable without wall-clock assertions).
``cost_hints`` reports which side won as ``hint_source`` ("measured" |
"default"), which ``QueryPlan.explain()`` surfaces.

The governing budget on TPU is VMEM, counted in padded (8, 128) tiles:
each grid cell holds the transposed (W, bn) code tile — W rounds up to 8
sublanes — plus the kernels' widest intermediate, the (lanes, 8, bq)
one-hot used for the histogram scatter / slot scatter, where ``lanes`` is
`bins` for pass 1 and `k` for pass 2 and bq rounds up to 128 lanes. We keep
that one-hot under ~2 MiB by shrinking bq, keep bq a sublane multiple (8),
take sub = 128 (the lane width: the kernels slice the code tile at sub
offsets along the lanes) and bn a sub multiple, and stream the dataset in
the largest bn that still double-buffers. On CPU the kernels run
interpreted (the grid lowers to an XLA loop), so smaller tiles bound trace
size instead of VMEM.

Since the fused select went single-shot (one Pallas grid owns ALL of N —
no engine-side chunk scan), the heuristic is also grid-wide aware: N/bn is
both the grid's streaming extent and the second dimension of the pass-1
block-min pruning summary ((Q/bq, N/bn) int32, one SMEM scalar per grid
cell). For large N we grow bn toward the code-tile VMEM budget so the
summary footprint and per-query-block grid length stay bounded instead of
scaling linearly with the datastore.
"""
from __future__ import annotations

import json
import os
import time

import jax

_SUBLANE = 8
_LANE = 128
# per-cell budget for the (lanes, 8, bq) int32 one-hot intermediate.
# CPU runs interpreted: no VMEM to respect, and runtime scales with the
# number of in-kernel iterations, so a fatter budget (bigger sub, fewer
# fori steps) is strictly faster there.
_ONEHOT_BYTES = {"tpu": 2 << 20, "cpu": 4 << 20, "gpu": 1 << 20}
# single-shot grids: cap the N-block count (summary second dim / grid
# extent per query block) by growing bn, up to this (W, bn) int32 code-tile
# VMEM budget. At the cap, the per-query-block SMEM rows of the
# mask/summary/run flags hold 1024 int32 (4 KiB) each. On TPU the grid is
# a hardware loop, so the cap only bounds the summary; interpreted (CPU)
# the grid UNROLLS into the program, so the cap is much tighter there —
# the in-cell fori over bn/sub stays rolled, making a big bn the cheap
# direction.
_MAX_N_BLOCKS = {"tpu": 1024, "cpu": 16, "gpu": 1024}
_CODE_TILE_BYTES = {"tpu": 4 << 20, "cpu": 1 << 20, "gpu": 2 << 20}


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _round_down(n: int, m: int) -> int:
    return max(m, n // m * m)


# ---------------------------------------------------------------------------
# the measured autotune cache
# ---------------------------------------------------------------------------

def _pow2_bucket(n: int) -> int:
    """Geometry bucketing for cache keys: round up to a power of two, so
    one measurement covers the whole bucket instead of every exact shape."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


class AutotuneCache:
    """Per-(backend, kind, geometry-bucket) measured block shapes.

    Entries live in one JSON file (``path``; default from the
    ``REPRO_AUTOTUNE_CACHE`` env var, empty -> in-memory only) shaped
    ``{key: {"bq":…,"bn":…,"sub":…,"us":…}}``. A corrupt or missing file
    degrades to an empty cache — defaults always work. Lookups sanitize
    entries back onto the kernels' tiling constraints (bq/sub sublane
    multiples, bn a sub multiple) so a hand-edited or stale file can bias
    performance but never produce an invalid grid."""

    def __init__(self, path: str | None = None):
        self.path = (os.environ.get("REPRO_AUTOTUNE_CACHE", "")
                     if path is None else path)
        self._entries: dict[str, dict] = {}
        self._loaded = False

    # -- persistence -------------------------------------------------------

    def _load(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        if not self.path or not os.path.exists(self.path):
            return
        try:
            with open(self.path) as f:
                data = json.load(f)
            if isinstance(data, dict):
                self._entries.update(
                    {k: v for k, v in data.items() if isinstance(v, dict)})
        except (OSError, ValueError):
            pass                     # corrupt cache == empty cache

    def save(self) -> None:
        if not self.path:
            return
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._entries, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    # -- lookup ------------------------------------------------------------

    @staticmethod
    def key(backend: str, kind: str, Q: int, N: int, W: int,
            lanes: int) -> str:
        return (f"{backend}/{kind}/q{_pow2_bucket(Q)}"
                f"n{_pow2_bucket(N)}w{max(int(W), 1)}l{_pow2_bucket(lanes)}")

    def get(self, backend: str, kind: str, Q: int, N: int, W: int,
            lanes: int) -> dict | None:
        self._load()
        return self._entries.get(self.key(backend, kind, Q, N, W, lanes))

    def put(self, backend: str, kind: str, Q: int, N: int, W: int,
            lanes: int, entry: dict, persist: bool = True) -> None:
        self._load()
        self._entries[self.key(backend, kind, Q, N, W, lanes)] = dict(entry)
        if persist:
            self.save()

    def clear(self) -> None:
        self._entries.clear()
        self._loaded = True

    def __len__(self) -> int:
        self._load()
        return len(self._entries)


_CACHE = AutotuneCache()


def autotune_cache() -> AutotuneCache:
    return _CACHE


def configure(path: str | None = None) -> AutotuneCache:
    """Rebind the process-wide cache (tests point it at a tmp file; ""
    keeps it purely in-memory). Returns the new cache."""
    global _CACHE
    _CACHE = AutotuneCache("" if path is None else path)
    return _CACHE


def _sane_topk_entry(entry: dict, N: int,
                     backend: str) -> tuple[int, int, int] | None:
    """Sanitize a measured (bq, bn, sub) back onto the kernels' tiling
    constraints; None when the entry is not a usable shape."""
    try:
        bq, bn, sub = int(entry["bq"]), int(entry["bn"]), int(entry["sub"])
    except (KeyError, TypeError, ValueError):
        return None
    if min(bq, bn, sub) <= 0:
        return None
    bq = _round_up(bq, _SUBLANE)
    # TPU: sub-tiles are lane slices of the (W, bn) code tile
    sub = (_LANE if backend == "tpu"
           else min(_round_up(sub, _SUBLANE), 256))
    bn = _round_up(bn, sub)
    return bq, bn, sub


def hint_source(backend: str, kind: str, Q: int, N: int, W: int,
                lanes: int) -> str:
    """"measured" when the cache holds a usable entry for this geometry
    bucket, else "default" (the static heuristics)."""
    ent = _CACHE.get(backend, kind, Q, N, W, lanes)
    if kind == "topk":
        return "measured" if (ent is not None
                              and _sane_topk_entry(ent, N, backend)
                              ) else "default"
    return "measured" if (ent is not None and ent.get("bn")) else "default"


def measure(runner, candidates, *, backend: str, kind: str, Q: int, N: int,
            W: int, lanes: int, reps: int = 3, timer=None,
            persist: bool = True) -> dict:
    """Time ``runner(candidate)`` over ``candidates`` and cache the winner.

    ``runner`` executes one kernel call for a candidate shape (the caller
    blocks on the result); ``timer`` defaults to ``time.perf_counter`` and
    is injectable so tests measure with a fake clock — deterministic, no
    wall-time assertions. Each candidate gets one warm-up call (compile)
    plus ``reps`` timed calls; the best median wins. Returns the cached
    entry. Nothing in this module calls ``measure`` implicitly."""
    timer = time.perf_counter if timer is None else timer
    best = None
    for cand in candidates:
        try:
            runner(cand)                       # warm-up / compile
            times = []
            for _ in range(max(reps, 1)):
                t0 = timer()
                runner(cand)
                times.append(timer() - t0)
            us = sorted(times)[len(times) // 2] * 1e6
        except Exception:                      # noqa: BLE001 — an invalid
            continue                           # candidate just loses
        if best is None or us < best[0]:
            best = (us, cand)
    if best is None:
        raise ValueError("no candidate shape ran successfully")
    us, cand = best
    entry = dict(cand)
    entry["us"] = round(us, 3)
    _CACHE.put(backend, kind, Q, N, W, lanes, entry, persist=persist)
    return entry


def topk_candidates(Q: int, N: int, W: int, lanes: int,
                    backend: str | None = None) -> list[dict]:
    """Candidate (bq, bn, sub) shapes for ``measure`` around the static
    heuristic: the default itself plus halved/doubled bn and sub variants,
    sanitized and deduplicated."""
    backend = backend or jax.default_backend()
    bq, bn, sub = _topk_blocks_default(Q, N, W, lanes, backend)
    raw = [(bq, bn, sub), (bq, bn * 2, sub), (bq, max(bn // 2, sub), sub),
           (bq, bn, max(sub // 2, _SUBLANE)),
           (max(bq // 2, _SUBLANE), bn, sub)]
    out, seen = [], set()
    for cand in raw:
        ok = _sane_topk_entry(dict(zip(("bq", "bn", "sub"), cand)), N,
                              backend)
        if ok and ok not in seen:
            seen.add(ok)
            out.append(dict(zip(("bq", "bn", "sub"), ok)))
    return out


def topk_blocks(Q: int, N: int, W: int, lanes: int,
                backend: str | None = None) -> tuple[int, int, int]:
    """(bq, bn, sub) for the two-pass counting-select kernels.

    ``lanes`` is the width of the per-element one-hot scatter: ``bins`` for
    the histogram pass, ``k`` for the emit pass. Both passes should be given
    the SAME (bq, bn, sub) (use lanes=max(bins, k)) so they stream the
    dataset in identical tiles — required for the block-min summary, whose
    (Q/bq, N/bn) tiling must mean the same tiles in both passes.

    A measured :class:`AutotuneCache` entry for this (backend, geometry
    bucket) overrides the static heuristic; with an empty cache the result
    is the deterministic seeded default below.
    """
    backend = backend or jax.default_backend()
    ent = _CACHE.get(backend, "topk", Q, N, W, lanes)
    if ent is not None:
        sane = _sane_topk_entry(ent, N, backend)
        if sane is not None:
            return sane
    return _topk_blocks_default(Q, N, W, lanes, backend)


def _topk_blocks_default(Q: int, N: int, W: int, lanes: int,
                         backend: str) -> tuple[int, int, int]:
    """The static VMEM heuristic — the cache's seeded default."""
    budget = _ONEHOT_BYTES.get(backend, 1 << 20)
    tpu = backend == "tpu"
    lanes = max(lanes, 1)

    # queries run along the lanes of the kernels' transposed tiles: on TPU
    # a full 128-lane query block (or the whole padded batch, if smaller)
    bq = min(_round_up(Q, _SUBLANE), _LANE if tpu else 32)
    # one-hot (lanes, 8, bq) int32 under budget, bq counted lane-padded;
    # extreme lanes (bins or k in the thousands) shrink bq (it only
    # amortizes the revisited output block) down to one sublane group
    padded = (lambda b: _round_up(b, _LANE)) if tpu else (lambda b: b)
    while bq > _SUBLANE and 4 * lanes * _SUBLANE * padded(bq) > budget:
        bq = _round_down(bq // 2, _SUBLANE)
    if tpu:
        sub = _LANE
    else:
        sub = min(_round_down(budget // (4 * bq * lanes), _SUBLANE), 256)
    # stream the dataset in big tiles: amortize the revisited output block
    bn_cap = 2048 if tpu else 512
    bn = min(_round_up(N, sub), _round_down(bn_cap, sub))
    # single-shot whole-datastore grid: once N/bn exceeds the block cap the
    # pruning summary and grid length dominate — grow bn (still a multiple
    # of sub) until the block count is bounded or the (W, bn) code tile,
    # W padded to 8 sublanes, hits its VMEM budget
    max_blocks = _MAX_N_BLOCKS.get(backend, 64)
    if N > bn * max_blocks:
        want = _round_up(-(-N // max_blocks), sub)
        rows = _round_up(max(W, 1), _SUBLANE) if tpu else max(W, 1)
        cap = _round_down(_CODE_TILE_BYTES.get(backend, 1 << 20)
                          // (4 * rows), sub)
        bn = max(bn, min(want, cap))
    return bq, bn, sub


# the fused select's pass 1 races in two levels from this many bins up
# (d + 1). On a v5e (experiments/race_levels.py, N = 2^26, Q = 128; PERF.md)
# both modes time within 1 % of each other at d = 64, where the second
# sweep over the codes costs what the histogram lanes save; two levels take
# 1.39x less time at d = 96, 1.87x at d = 128 and 2.04x at d = 256.
_TWO_LEVEL_MIN_BINS = 97


def race_lanes(bins: int, shift: int) -> int:
    """Histogram lanes of a pass-1 call that counts ``dist >> shift``
    over distances [0, bins): ceil(bins / 2^shift)."""
    return ((bins - 1) >> shift) + 1


def race_shift(bins: int) -> int:
    """The coarse shift s of the fused select's pass 1 (``ops._race``), 0
    for the one-level race over all ``bins``. A function of the width
    alone: the coarse level counts ``dist >> s`` into ceil(bins / 2^s)
    lanes, the fine level a window of 2^s distances (one coarse bucket),
    and s minimizes the lanes counted per pair, their sum (about log2 of
    sqrt(bins); on a tie the narrower window, which measured faster at
    d = 128)."""
    if bins < _TWO_LEVEL_MIN_BINS:
        return 0
    return min(range(1, (bins - 1).bit_length()),
               key=lambda s: (race_lanes(bins, s) + (1 << s), s))


def layout_blocks(Q: int, N: int, W: int, lanes: int, bucket_rows: int,
                  backend: str | None = None) -> tuple[int, int, int]:
    """(bq, bn, sub) for the MASKED select over a bucket-clustered layout
    (core/layout.py).

    Same VMEM heuristic as ``topk_blocks``, but bn is additionally pulled
    toward the bucket size (rounded up to a sub multiple — "round buckets
    up to tile multiples"): the enable mask's granularity is the data
    block, and a block much larger than a bucket drags several neighbor
    buckets into every probe's candidate set, while a block much smaller
    just grows the (tiny) mask. Overrides ``topk_blocks``'s large-N bn
    growth when the two fight — mask resolution beats summary compactness
    on the probed path (the mask IS the point there)."""
    bq, bn, sub = topk_blocks(Q, N, W, lanes, backend=backend)
    if bucket_rows and bucket_rows > 0:
        bn = max(sub, min(bn, _round_up(bucket_rows, sub)))
    return bq, bn, sub


def approx_blocks(Q: int, N: int, W: int,
                  backend: str | None = None) -> int:
    """Data-block rows ``bn`` for the approximate partial-reduce select
    (``kernels/approx_select.py``): each block's (Q, bn) MXU score tile is
    reduced to L candidates before the merge. Bigger blocks mean fewer,
    larger matmuls (and a higher recall at the same L — fewer chances for
    true neighbors to collide); smaller blocks bound the score tile. The
    seeded default targets ~32 blocks with a lane-aligned floor; a measured
    cache entry (kind="approx") overrides it."""
    backend = backend or jax.default_backend()
    ent = _CACHE.get(backend, "approx", Q, N, W, 1)
    if ent is not None:
        try:
            bn = int(ent["bn"])
        except (KeyError, TypeError, ValueError):
            bn = 0
        if bn > 0:
            return min(_round_up(bn, _LANE), 1 << 16)
    bn = _round_up(max(-(-max(N, 1) // 32), _LANE), _LANE)
    return min(bn, 8192)


def cost_hints(Q: int, N: int, W: int, lanes: int, *, path: str = "fused",
               chunk: int = 0, bucket_rows: int = 0,
               backend: str | None = None) -> dict:
    """Geometry + predicted per-call footprints for ``QueryPlan.explain()``.

    Computed by the SAME heuristics the kernels consult (``topk_blocks`` /
    ``layout_blocks`` / ``distance_blocks``), so the summary is exact for
    the fused paths, and policy stays here rather than in the planner.
    Byte counts are per query batch: ``codes_bytes_streamed`` is HBM->VMEM
    code traffic (fused reads the codes once per pass per query block),
    ``onehot_bytes`` is the widest in-kernel intermediate the VMEM budget
    sized, ``summary_bytes`` the pass-1 block-min pruning table."""
    backend = backend or jax.default_backend()
    if path in ("fused", "fused_scan"):
        n_eff = min(chunk, N) if (path == "fused_scan" and chunk) else N
        if bucket_rows:
            bq, bn, sub = layout_blocks(Q, n_eff, W, lanes, bucket_rows,
                                        backend=backend)
        else:
            bq, bn, sub = topk_blocks(Q, n_eff, W, lanes, backend=backend)
        q_pad, n_pad = _round_up(Q, bq), _round_up(n_eff, bn)
        grid = (q_pad // bq, n_pad // bn)
        hints = {
            "bq": bq, "bn": bn, "sub": sub, "grid": list(grid),
            "codes_bytes_streamed": 2 * 4 * W * n_pad * grid[0],
            "onehot_bytes": 4 * max(lanes, 1) * _SUBLANE * bq,
            "summary_bytes": 4 * grid[0] * grid[1],
            "hist_bytes": 4 * Q * max(lanes, 1),
            "hint_source": hint_source(backend, "topk", Q, n_eff, W, lanes),
        }
        if path == "fused_scan":
            hints["n_scan_steps"] = -(-N // max(n_eff, 1))
        return hints
    # materializing paths: the (Q, chunk) distance tile is the cost
    c = min(chunk or N, N)
    return {
        "codes_bytes_streamed": 4 * W * N,
        "distance_tile_bytes": 4 * Q * c,
        "distance_total_bytes": 4 * Q * N,
        "hint_source": "default",
    }


def merge_fanout(n_shards: int) -> int:
    """Default hist_tree group width: roughly sqrt(n_shards) rounded to a
    power of two, so the intra-host (level-0) and inter-host (tree) halves
    of the merge carry balanced group sizes. Below 4 shards a tree cannot
    beat the flat psum — return 0 (flat)."""
    if n_shards < 4:
        return 0
    f = 2
    while f * f < n_shards:
        f *= 2
    return f


def tree_levels(n_shards: int, fanout: int) -> int:
    """Number of reduction rounds ``ops._tree_psum`` runs for this shard
    count and fanout (divisible rounds + the remainder round). Mirrors the
    kernel's loop exactly so ``shard_hints`` predicts the real schedule."""
    if fanout < 2 or n_shards < 2:
        return 1 if n_shards > 1 else 0
    levels, s = 0, 1
    while s * fanout <= n_shards and n_shards % (s * fanout) == 0:
        levels += 1
        s *= fanout
    if s < n_shards:
        levels += 1
    return levels


def shard_hints(Q: int, k: int, bins: int, n_shards: int, *,
                k_local: int | None = None,
                strategy: str = "hist_merge",
                fanout: int = 0) -> dict:
    """Shard geometry + predicted CROSS-DEVICE merge traffic per query
    batch, for ``QueryPlan.explain()`` on sharded plans.

    ``hist_merge`` (the distributed counting select) moves exactly three
    tiny tensors between devices: the (Q, bins) int32 partial-histogram
    psum (the coarse and fine ones of a two-level race, ``race_shift``),
    the (Q, 2)-per-shard slot-base all-gather, and the (Q, k) x2
    disjoint-slot output psum — O(Q·bins), independent of n_shards·k.
    ``hist_tree`` moves the SAME tensors but reduces them hierarchically:
    level 0 is the intra-host group psum, the remaining ``tree_levels - 1``
    rounds are the inter-host tree — per-hop traffic shrinks from one
    n_shards-wide reduction to ``fanout``-wide exchanges, reported split
    into ``hist_tree_intra_bytes`` / ``hist_tree_inter_bytes``.
    ``concat_sort`` (the legacy hierarchical merge) all-gathers every
    shard's (k' dists, k' ids): O(n_shards·Q·k') candidate bytes. All are
    reported so the ratios are inspectable whatever the plan chose."""
    k_local = k if (k_local is None or k_local <= 0) else k_local
    shift = race_shift(bins)
    # pass 1's psums: one (Q, bins) histogram, or a two-level race's
    # coarse and fine ones
    hist_psum = 4 * Q * (race_lanes(bins, shift) + (1 << shift)
                         if shift else bins)
    counts_gather = 2 * 4 * Q * n_shards
    output_psum = 2 * 4 * Q * k
    hist_total = hist_psum + counts_gather + output_psum
    concat_total = 2 * 4 * Q * k_local * n_shards
    eff_fanout = fanout if fanout >= 2 else (merge_fanout(n_shards) or 2)
    levels = max(tree_levels(n_shards, eff_fanout), 1)
    per_level = hist_psum + output_psum
    tree_intra = per_level
    tree_inter = (levels - 1) * per_level
    tree_total = tree_intra + tree_inter + counts_gather
    return {
        "n_shards": n_shards,
        "strategy": strategy,
        "merge_bytes": (concat_total if strategy == "concat_sort"
                        else tree_total if strategy == "hist_tree"
                        else hist_total),
        "hist_merge_bytes": hist_total,
        "hist_psum_bytes": hist_psum,
        "counts_gather_bytes": counts_gather,
        "output_psum_bytes": output_psum,
        "concat_sort_bytes": concat_total,
        "fanout": eff_fanout if strategy == "hist_tree" else fanout,
        "tree_levels": levels,
        "hist_tree_intra_bytes": tree_intra,
        "hist_tree_inter_bytes": tree_inter,
        "hist_tree_bytes": tree_total,
    }


def distance_blocks(Q: int, N: int, W: int,
                    backend: str | None = None) -> tuple[int, int]:
    """(bq, bn) for the materializing (Q, N) distance kernel: the (bq, bn)
    int32 output tile plus the (bq, bn, W) xor intermediate dominate."""
    # same tile on every backend for now: on TPU it fits the (bq, bn, W) xor
    # intermediate comfortably in VMEM; interpreted, it only bounds trace
    # length. Split per backend here when the TPU numbers diverge.
    bq, bn = 128, 512
    bq = min(bq, _round_up(Q, _SUBLANE))
    bn = min(bn, _round_up(N, _LANE))
    return bq, bn
