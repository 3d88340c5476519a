"""The one traffic generator. A configuration states the distribution its
codes are drawn from (``codes`` in ``configs/<config>.json``); a traffic mix
(``traffic/<mix>.json``) states the query batches sent to it. Queries are
held-out rows: fresh draws from the store's own distribution, as a search
workload's queries come from the same data as its store. Every seed gets the
same sizes, so the seed changes which codes arrive, not how much work they
are.

Code distributions (``codes``):

- ``{"kind": "uniform"}``: every bit 1 with probability 1/2, independently.
- ``{"kind": "clustered", "centres": C, "flip_log2": f}``: each row is one of
  ``C`` centres (uniform random codes, shared by the store and its queries),
  chosen uniformly, with each bit flipped with probability 2**-f (the AND of
  ``f`` random words).

Traffic mixes (``kind``):

- ``query_batches``: back-to-back batches of ``batch`` queries from one
  closed-loop client; ``pool_batches`` distinct batches are drawn on the
  device during set-up and cycled.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp


def _draw(dist_key, row_key, n: int, words: int, dist: tuple):
    kind, params = dist[0], dict(dist[1:])
    if kind == "uniform":
        return jax.random.bits(row_key, (n, words), jnp.uint32)
    if kind == "clustered":
        centres = jax.random.bits(dist_key, (params["centres"], words),
                                  jnp.uint32)
        k_which, k_flip = jax.random.split(row_key)
        which = jax.random.randint(k_which, (n,), 0, params["centres"])
        mask = functools.reduce(jnp.bitwise_and, [
            jax.random.bits(jax.random.fold_in(k_flip, i), (n, words),
                            jnp.uint32)
            for i in range(params["flip_log2"])])
        return centres[which] ^ mask
    raise ValueError(f"unknown code distribution {kind!r}")


def _dist_key(dist: dict) -> tuple:
    return (dist["kind"],) + tuple(sorted(
        (k, v) for k, v in dist.items() if k != "kind"))


def draw_codes(dist_key, row_key, n: int, words: int, dist: dict,
               sharding=None) -> jax.Array:
    """(n, words) uint32 codes of distribution ``dist``, made on the
    device(s) in one call. ``dist_key`` fixes the distribution (its
    centres), ``row_key`` the rows drawn from it."""
    fn = jax.jit(_draw, static_argnums=(2, 3, 4), out_shardings=sharding)
    return fn(dist_key, row_key, n, words, _dist_key(dist))


def query_pool(dist_key, row_key, words: int, dist: dict, traffic: dict,
               sharding=None) -> List[jax.Array]:
    """``pool_batches`` query batches (each (batch, words) uint32) drawn
    from the store's distribution on the device, split once so the window
    indexes nothing."""
    assert traffic["kind"] == "query_batches", traffic["kind"]
    b, q = traffic["pool_batches"], traffic["batch"]
    pool = draw_codes(dist_key, row_key, b * q, words, dist).reshape(b, q,
                                                                     words)
    if sharding is not None:
        pool = jax.device_put(pool, sharding)
    return [pool[i] for i in range(b)]
