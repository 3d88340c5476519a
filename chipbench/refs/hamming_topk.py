"""Exact Hamming k-nearest neighbours in plain jax.numpy, and the comparison
of a search's answers with it.

Codes are (N, W) uint32 words; bit i of a code is bit i % 32 of word
i // 32. The answer to a query is its k nearest rows by Hamming distance,
ascending. Rows tied at the k-th distance may be any of the tied rows, so
an answer is correct when its distances equal the reference's, every id is
a distinct row of the store, and each id lies at the distance reported
beside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 1 << 16


def _popcount_dist(q, x):
    """(..., W) x (..., W) -> summed popcount of the XOR, int32."""
    return jnp.sum(jax.lax.population_count(q ^ x).astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "shards", "chunk",
                                             "n_valid"))
def topk(codes, queries, k: int, shards: int = 1, chunk: int = CHUNK,
         n_valid: int = 0):
    """(dists, ids), each (Q, k) int32: ascending distance, ties by row
    index. The rows split into ``shards`` contiguous slices (a row-sharded
    store is read where it lives), each scanned in ``chunk``-row steps.
    ``n_valid`` > 0 leaves rows with index >= n_valid out of the search
    (the control: a search that skips part of the store)."""
    n, w = codes.shape
    chunk = min(chunk, n // shards)
    per = n // shards // chunk
    if per * shards * chunk != n:
        raise ValueError(f"{n} rows do not split into {shards} x {chunk}")
    x = codes.reshape(shards, per, chunk, w)
    nq = queries.shape[0]
    local = jnp.arange(chunk, dtype=jnp.int32)
    base = (jnp.arange(shards, dtype=jnp.int32) * (per * chunk))[:, None, None]
    big = jnp.iinfo(jnp.int32).max

    def step(carry, c):
        best_d, best_i = carry                                  # (S, Q, k)
        dist = _popcount_dist(queries[None, :, None, :],
                              x[:, c][:, None, :, :])          # (S, Q, chunk)
        gid = c * chunk + local[None, None, :] + base
        if n_valid:
            dist = jnp.where(gid < n_valid, dist, w * 32 + 1)
        neg, _ = jax.lax.top_k(-(dist * chunk + local), k)      # unique keys
        cd, ci = (-neg) // chunk, (-neg) % chunk + c * chunk + base
        dd = jnp.concatenate([best_d, cd], axis=-1)
        ii = jnp.concatenate([best_i, ci], axis=-1)
        dd, ii = jax.lax.sort((dd, ii), num_keys=2)
        return (dd[..., :k], ii[..., :k]), None

    init = jnp.full((shards, nq, k), big, jnp.int32)
    (bd, bi), _ = jax.lax.scan(step, (init, init), jnp.arange(per))
    dd = bd.transpose(1, 0, 2).reshape(nq, shards * k)
    ii = bi.transpose(1, 0, 2).reshape(nq, shards * k)
    dd, ii = jax.lax.sort((dd, ii), num_keys=2)
    return dd[:, :k], ii[:, :k]


@jax.jit
def wrong_rows(codes, queries, got_d, got_i, ref_d):
    """Number of query rows whose answer is not an exact k-NN answer (see
    the module doc). got_*: (Q, k) as the search returned them."""
    n = codes.shape[0]
    valid = (got_i >= 0) & (got_i < n)
    true_d = _popcount_dist(queries[:, None, :],
                            codes[jnp.clip(got_i, 0, n - 1)])
    dists_ok = jnp.all(jnp.sort(got_d, axis=-1) == ref_d, axis=-1)
    ids_ok = jnp.all(valid & (true_d == got_d), axis=-1)
    s = jnp.sort(got_i, axis=-1)
    distinct = jnp.all(s[:, 1:] != s[:, :-1], axis=-1)
    return jnp.sum(~(dists_ok & ids_ok & distinct)).astype(jnp.int32)
