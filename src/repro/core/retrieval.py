"""kNN-LM retrieval: the paper's similarity-search engine as a first-class
serving feature of every backbone.

The datastore maps binary-quantized hidden states -> next-token ids
(Khandelwal et al.-style). At decode time the current hidden state is ITQ-
encoded, searched against the mesh-sharded datastore (Hamming kNN — the
paper's engine), and the neighbor distribution is interpolated with the LM
softmax.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig, RetrievalConfig
from repro.core import binary, layout as layout_mod, plan as plan_mod, quantize


class DataStore(NamedTuple):
    codes: jax.Array        # (N, W) uint32 packed ITQ codes of hidden states
    values: jax.Array       # (N,) int32 next-token ids
    itq: quantize.ITQParams
    # optional bucket-clustered reorder of codes (core/layout.py): the
    # single-device fused select streams layout.codes and maps winners back
    # to original ids, so `values` never needs reordering
    layout: Optional[layout_mod.BucketLayout] = None
    # the hamming-prefix key bit positions the layout was bucketed by,
    # when the builder FROZE them (mutable stores must: re-deriving the
    # "most balanced" bits from mutated codes drifts away from how the
    # arena is actually bucketed, silently mis-aiming every degraded
    # probe). None -> probe_key_positions recomputes them, which is exact
    # for one-shot static builds.
    key_positions: Optional[jax.Array] = None


def _maybe_layout(codes: jax.Array, code_bits: int, rcfg_layout: str,
                  layout_buckets: int) -> Optional[layout_mod.BucketLayout]:
    if rcfg_layout == "none":
        return None
    assert rcfg_layout == "hamming_prefix", rcfg_layout
    return layout_mod.build_layout(codes, code_bits,
                                   n_buckets=layout_buckets or None)


def build_datastore(hidden: jax.Array, next_tokens: jax.Array, code_bits: int,
                    itq_iters: int = 20, key=None, layout: str = "none",
                    layout_buckets: int = 0) -> DataStore:
    """hidden: (N, d_model) f32; next_tokens: (N,) int32. ``layout``/
    ``layout_buckets`` follow RetrievalConfig's fields of the same name."""
    itq = quantize.itq_train(hidden, code_bits, iters=itq_iters, key=key)
    codes = binary.pack_bits(quantize.itq_encode(hidden, itq))
    return DataStore(codes=codes, values=next_tokens.astype(jnp.int32),
                     itq=itq,
                     layout=_maybe_layout(codes, code_bits, layout,
                                          layout_buckets))


def synthetic_datastore(cfg: ModelConfig, n: Optional[int] = None, key=None) -> DataStore:
    """Deterministic random datastore sized per the arch's RetrievalConfig
    (used by serve_step dry-runs and benchmarks)."""
    r = cfg.retrieval
    n = n if n is not None else r.datastore_size
    key = key if key is not None else jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    W = binary.padded_words(r.code_bits)
    codes = jax.random.bits(k1, (n, W), jnp.uint32)       # all 32 bits
    values = jax.random.randint(k2, (n,), 0, cfg.vocab_size, jnp.int32)
    itq = quantize.ITQParams(
        mean=jnp.zeros((cfg.d_model,), jnp.float32),
        proj=jnp.eye(cfg.d_model, r.code_bits, dtype=jnp.float32),
        rot=jnp.eye(r.code_bits, dtype=jnp.float32))
    return DataStore(codes=codes, values=values, itq=itq,
                     layout=_maybe_layout(codes, r.code_bits, r.layout,
                                          r.layout_buckets))


def plan_for_store(store: DataStore, rcfg: RetrievalConfig, q: int,
                   mesh: Optional[Mesh] = None, axes: Sequence[str] = (),
                   method: str = "xor", select: Optional[str] = None,
                   recall_target: Optional[float] = None
                   ) -> plan_mod.QueryPlan:
    """The QueryPlan ``knn_logits`` executes against this store.

    Select precedence: explicit ``select`` argument > ``rcfg.plan`` (when
    not "auto") > ``rcfg.select``; ``rcfg.force_plan`` overrides apply
    last. ``rcfg.layout != "none"`` demands a layout (``layout_policy=
    "require"``): the planner streams the prebuilt store layout when one
    exists, else falls back to a per-call re-sort (with a warning —
    prebuild via ``build_datastore(..., layout=...)`` to amortize).
    Sharded, a prebuilt GLOBAL layout cannot follow the shard slicing, so
    the planner only opts into per-shard re-sorting when the config asks —
    a prebuilt store layout alone never opts the decode hot path into that
    cost. Exact sharded serving (``rcfg.local_k >= rcfg.k``) rides the
    hist_merge distributed counting select — O(Q·bins) cross-device counts
    instead of O(shards·Q·k) gathered candidates; ``local_k < k`` keeps
    the statistical concat/sort reduction. The runtime server logs this
    plan (merge strategy and predicted traffic included) per store at
    startup."""
    if select is None:
        select = rcfg.plan if rcfg.plan != "auto" else rcfg.select
    if recall_target is None:
        recall_target = rcfg.recall_target
    policy = "require" if rcfg.layout != "none" else "auto"
    n, w = store.codes.shape
    if mesh is not None and axes:
        n_dev = 1
        for a in axes:
            n_dev *= mesh.shape[a]
        # a prebuilt GLOBAL layout cannot follow the shard slicing, so the
        # sharded stats deliberately omit it (layout_policy still carries
        # the config's demand, satisfied per shard via local_sort)
        stats = plan_mod.stats_for(n, rcfg.code_bits, w, q, k=rcfg.k,
                                   n_shards=n_dev)
        return plan_mod.plan_sharded(
            stats, rcfg.k, axes=tuple(axes), k_local=rcfg.local_k,
            select=select, method=method, chunk=rcfg.chunk_size,
            layout_policy=policy, recall_target=recall_target,
            force=rcfg.force_plan)
    stats = plan_mod.stats_for(n, rcfg.code_bits, w, q, k=rcfg.k,
                               layout=store.layout)
    return plan_mod.plan_local(
        stats, rcfg.k, select=select, method=method, chunk=rcfg.chunk_size,
        layout_policy=policy, recall_target=recall_target,
        force=rcfg.force_plan)


def log_store_plan(store: DataStore, rcfg: RetrievalConfig, q: int,
                   logger, mesh: Optional[Mesh] = None,
                   axes: Sequence[str] = ()) -> plan_mod.QueryPlan:
    """Resolve and log the store's QueryPlan (serving-side ``explain()``).

    The runtime server calls this once per store at startup; pass the
    mesh/axes the serve step will search with so the logged plan is the
    one decode actually runs (without them it is the store's LOCAL plan).
    Sharded plans additionally log the merge strategy and its predicted
    cross-device traffic (tuning.shard_hints via plan.geometry())."""
    p = plan_for_store(store, rcfg, q, mesh=mesh, axes=axes)
    logger.info("retrieval store: %d entries, active plan %s",
                store.codes.shape[0], p.compact())
    if p.merge.kind == "sharded":
        m = p.geometry()["merge"]
        logger.info(
            "retrieval shard merge: %s over %d shards, predicted merge "
            "traffic %d B/batch (hist_merge %d B vs concat_sort %d B)",
            m["strategy"], m["n_shards"], m["merge_bytes"],
            m["hist_merge_bytes"], m["concat_sort_bytes"])
    logger.debug("retrieval plan detail:\n%s", p.explain_str())
    return p


def probe_key_positions(store: DataStore,
                        rcfg: RetrievalConfig) -> Optional[jax.Array]:
    """The hamming-prefix key-bit positions of ``store.layout``.

    ``build_layout``'s pure-Hamming fallback keys buckets by the
    ``log2(n_buckets)`` most balanced bit positions — a deterministic
    function of the codes, so recomputing the selection here reproduces
    the exact bucket ids the layout was clustered by. Returns None when
    the store has no layout or a non-power-of-two bucket count (i.e. a
    layout whose assignment did not come from the hamming-prefix key, such
    as an external k-means assign): degraded probing is unavailable there.
    """
    lay = store.layout
    if lay is None:
        return None
    if store.key_positions is not None:
        return store.key_positions     # frozen at build (mutable stores)
    bits = lay.n_buckets.bit_length() - 1
    if (1 << bits) != lay.n_buckets:
        return None
    _, positions = layout_mod.hamming_prefix_assign(store.codes,
                                                    rcfg.code_bits, bits)
    return positions


def degraded_plan_for_store(store: DataStore, rcfg: RetrievalConfig, q: int,
                            nprobe: int) -> plan_mod.QueryPlan:
    """The reduced-nprobe masked plan a degradation rung serves with:
    hamming-prefix key probing feeds the block-mask fused kernels, same
    shape as an IVF probe but with no float centroids."""
    stats = plan_mod.stats_for(store.codes.shape[0], rcfg.code_bits,
                               store.codes.shape[1], q, k=rcfg.k,
                               layout=store.layout)
    return plan_mod.plan_index(stats, rcfg.k, kind="hamming_prefix",
                               nprobe=nprobe)


def _bucket_probe(q_codes: jax.Array, positions: jax.Array, n_buckets: int,
                  nprobe: int, d: int) -> jax.Array:
    """(Q, W) packed queries -> (Q, nprobe) bucket ids, nearest first.
    Thin alias for :func:`index.hamming_prefix_probe` — the probe ranking
    is index policy, shared with the mutable store's degraded path."""
    from repro.core import index as index_mod
    return index_mod.hamming_prefix_probe(q_codes, positions, n_buckets,
                                          nprobe, d)


def knn_search(store: DataStore, hidden: jax.Array, rcfg: RetrievalConfig,
               mesh: Optional[Mesh] = None, axes: Sequence[str] = (),
               method: str = "xor", select: Optional[str] = None,
               recall_target: Optional[float] = None, nprobe: int = 0,
               probe_positions: Optional[jax.Array] = None):
    """hidden: (Q, d_model) -> (q_codes (Q, W) uint32, dists (Q, k), ids
    (Q, k)): the keys' ITQ codes (under the ``knnlm.encode`` scope) and
    their neighbours in the store.

    A thin plan-builder: ``plan_for_store`` resolves the select path,
    layout usage and sharded merge from the store's stats and the config
    (``rcfg.plan`` / ``rcfg.force_plan``; the ``select`` argument is a
    legacy per-call forced override), and ``plan.execute`` runs the staged
    search. "fused" streams the whole datastore through one two-pass
    Pallas invocation without ever materializing distances —
    ``rcfg.chunk_size`` only granulates the materializing/'fused_scan'
    scans. Inspect the decision with ``plan_for_store(...).explain()``.

    ``nprobe > 0`` with ``probe_positions`` (``probe_key_positions``)
    switches to the DEGRADED masked search the serving ladder downshifts
    to: only the ``nprobe`` nearest hamming-prefix buckets are scanned.
    ``recall_target`` overrides ``rcfg.recall_target`` for the approx tier
    (the ladder's approx rung serves at a degraded target)."""
    with jax.named_scope("knnlm.encode"):
        q_codes = binary.pack_bits(quantize.itq_encode(hidden, store.itq))
    if nprobe > 0 and store.layout is not None and probe_positions is not None:
        p = degraded_plan_for_store(store, rcfg, hidden.shape[0], nprobe)
        probe = _bucket_probe(q_codes, probe_positions,
                              store.layout.n_buckets, nprobe, rcfg.code_bits)
        dists, ids = plan_mod.execute(p, q_codes, layout=store.layout,
                                      probe=probe)
    else:
        p = plan_for_store(store, rcfg, hidden.shape[0], mesh=mesh,
                           axes=axes, method=method, select=select,
                           recall_target=recall_target)
        if p.merge.kind == "sharded":
            dists, ids = plan_mod.execute(p, q_codes, codes=store.codes,
                                          mesh=mesh)
        else:
            dists, ids = plan_mod.execute(p, q_codes, codes=store.codes,
                                          layout=store.layout)
    return q_codes, dists, ids


def knn_distribution(store: DataStore, dists: jax.Array, ids: jax.Array,
                     rcfg: RetrievalConfig, vocab: int,
                     temperature: float = 8.0) -> jax.Array:
    """(dists, ids) (Q, k) -> the neighbour log-distribution (Q, vocab):
    softmax of -distance / ``temperature`` over the neighbours, summed per
    stored next token (the vocabulary scatter, scope ``knnlm.logits``)."""
    with jax.named_scope("knnlm.logits"):
        n = store.values.shape[0]
        # fewer than k valid neighbors -> the engine pads with sentinels
        # (full scans: dist = d+1, id >= N; masked probes: id = -1): they
        # must not receive softmax weight or vote for values[N-1]; mask
        # them out of the neighbor distribution (an all-invalid row
        # degenerates to p = 0 and hits the log floor below)
        valid = (ids >= 0) & (ids < n) & (dists <= rcfg.code_bits)   # (Q, k)
        neighbor_tokens = store.values[jnp.clip(ids, 0, n - 1)]      # (Q, k)
        w = jax.nn.softmax(
            jnp.where(valid, -dists.astype(jnp.float32) / temperature,
                      -jnp.inf), axis=-1)
        w = jnp.where(valid, w, 0.0)
        q = dists.shape[0]
        p = jnp.zeros((q, vocab), jnp.float32)
        p = p.at[jnp.arange(q)[:, None], neighbor_tokens].add(w)
        return jnp.log(jnp.maximum(p, 1e-9))


def knn_logits(store: DataStore, hidden: jax.Array, rcfg: RetrievalConfig,
               vocab: int, mesh: Optional[Mesh] = None,
               axes: Sequence[str] = (), method: str = "xor",
               temperature: float = 8.0,
               select: Optional[str] = None,
               recall_target: Optional[float] = None,
               nprobe: int = 0,
               probe_positions: Optional[jax.Array] = None) -> jax.Array:
    """hidden: (Q, d_model) -> neighbor log-distribution (Q, vocab):
    ``knn_search`` then ``knn_distribution`` (see both)."""
    _, dists, ids = knn_search(store, hidden, rcfg, mesh=mesh, axes=axes,
                               method=method, select=select,
                               recall_target=recall_target, nprobe=nprobe,
                               probe_positions=probe_positions)
    return knn_distribution(store, dists, ids, rcfg, vocab, temperature)


def interpolate(lm_logits: jax.Array, knn_log_probs: jax.Array,
                lam: float) -> jax.Array:
    """log((1-lam) softmax(lm) + lam exp(knn_log_probs)), float32."""
    with jax.named_scope("knnlm.mix"):
        lm_logp = jax.nn.log_softmax(lm_logits.astype(jnp.float32), axis=-1)
        return jnp.logaddexp(lm_logp + jnp.log1p(-lam),
                             knn_log_probs + jnp.log(lam))
