"""Brute-force tile counts for the fused kernels' pruning telemetry, made
from distances alone (numpy), and the clustered store the tile-count tests
search.

A pass-1 tile runs iff it is enabled. A pass-2 tile can hold a winner iff
it is enabled and some valid row in it lies at or below the widest r* of
its query block, r* being each query's k-th smallest distance over its
candidate rows. The block's distances are those of every query row the
kernels see: the wrapper pads the batch to a block multiple with zero
codes, and those rows enter the block minimum like any other.
"""
from __future__ import annotations

import re

import numpy as np

SCOPE = re.compile(r'op_name="[^"]*?(knn\.[a-z0-9_.]+)')


def scopes(hlo: str) -> set:
    """The ``knn.*`` named scopes in a compiled HLO's op_name metadata."""
    return set(SCOPE.findall(hlo))


def pack(bits: np.ndarray) -> np.ndarray:
    """(n, d) {0,1} -> (n, d/32) uint32, bit p at word p // 32, bit p % 32
    (``core.binary.pack_bits``)."""
    n, d = bits.shape
    b = bits.reshape(n, d // 32, 32).astype(np.uint64)
    return (b << np.arange(32, dtype=np.uint64)).sum(-1).astype(np.uint32)


def clustered(seed: int, n: int, d: int = 64, rows_per_cluster: int = 1024):
    """(codes (n, d/32), queries (40, d/32)) uint32: clusters of
    ``rows_per_cluster`` contiguous rows around random centres, bits flipped
    at p = 1/16; 32 queries near clusters 0 and 1, 8 near cluster 5."""
    rng = np.random.default_rng(seed)
    centres = rng.integers(0, 2, (-(-n // rows_per_cluster), d))
    xb = centres[np.arange(n) // rows_per_cluster] ^ (rng.random((n, d)) < 1 / 16)
    qc = np.array([0] * 16 + [1] * 16 + [5] * 8)
    qb = centres[qc] ^ (rng.random((qc.size, d)) < 1 / 16)
    return pack(xb), pack(qb)


def distances(q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """(Q, W) x (N, W) uint32 -> (Q, N) Hamming distances."""
    v = np.bitwise_xor(q[:, None, :], x[None, :, :])
    return np.unpackbits(v.view(np.uint8), axis=-1).sum(-1, dtype=np.int64)


def radius(dist: np.ndarray, cand: np.ndarray, k: int) -> np.ndarray:
    """Per query, the k-th smallest distance over its candidate rows
    (``cand`` (Q, N) bool); with fewer candidates the largest, with none 0."""
    r = np.zeros(dist.shape[0], np.int64)
    for i in range(dist.shape[0]):
        c = np.sort(dist[i][cand[i]])
        if c.size:
            r[i] = c[min(k, c.size) - 1]
    return r


def probe_enabled(starts: np.ndarray, probe: np.ndarray, bq: int, bn: int,
                  nq: int, nj: int) -> np.ndarray:
    """(nq, nj) bool: tile (i, j) is enabled iff a query of block i probes a
    non-empty bucket whose row range meets data block j."""
    en = np.zeros((nq, nj), bool)
    for qi, buckets in enumerate(probe):
        for b in buckets:
            lo, hi = int(starts[b]), int(starts[b + 1])
            if hi > lo:
                en[qi // bq, lo // bn:(hi - 1) // bn + 1] = True
    return en


def tile_counts(q: np.ndarray, x: np.ndarray, k: int, bq: int, bn: int,
                n_valid: int | None = None, enabled: np.ndarray | None = None,
                r_star: np.ndarray | None = None, shift: int = 0) -> dict:
    """Tiles one two-pass call over codes ``x`` (in the order the kernels
    stream them) skips: {"blocks_total", "p1_blocks_skipped",
    "p1_fine_blocks_skipped", "blocks_skipped"}. ``r_star`` (Q,) defaults
    to each query's own radius over its candidate rows; the sharded select
    passes the global one. ``shift``: the race's coarse shift (0: one
    level, no fine call to skip anything). The fine call runs an enabled
    tile iff its block minimum (bins = d + 1 where it holds no valid row)
    lies at or below the top of the widest window of its query block, a
    window being the 2^shift distances of the coarse bucket of r*."""
    Q, W = q.shape
    N = x.shape[0]
    nv = N if n_valid is None else n_valid
    nq, nj = -(-Q // bq), -(-N // bn)
    qp = np.zeros((nq * bq, W), np.uint32)
    qp[:Q] = q
    dist = distances(qp, x)
    valid = np.arange(N) < nv
    en = np.ones((nq, nj), bool) if enabled is None else enabled
    row_en = np.repeat(en, bn, axis=1)[:, :N]               # (nq, N)
    if r_star is None:
        cand = valid[None, :] & row_en[np.arange(Q) // bq]
        r_star = radius(dist[:Q], cand, k)
    width = 1 << shift
    top = (np.asarray(r_star) >> shift << shift) + width - 1
    run = np.zeros((nq, nj), bool)
    fine = np.zeros((nq, nj), bool)
    for i in range(nq):
        widest = r_star[i * bq:(i + 1) * bq].max()
        for j in range(nj):
            rows = np.arange(j * bn, min((j + 1) * bn, N))
            rows = rows[valid[rows]]
            bmin = (dist[i * bq:(i + 1) * bq, rows].min() if rows.size
                    else W * 32 + 1)
            if en[i, j]:
                run[i, j] = rows.size > 0 and bmin <= widest
                fine[i, j] = bmin <= top[i * bq:(i + 1) * bq].max()
    return {"blocks_total": nq * nj,
            "p1_blocks_skipped": int((~en).sum()),
            "p1_fine_blocks_skipped": int((~fine).sum()) if shift else 0,
            "blocks_skipped": int((~run).sum())}
