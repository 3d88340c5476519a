"""Binary codes: packing and Hamming distance.

The paper encodes one dataset vector per NFA "Hamming macro". On TPU the
analogous resource decision is *how the bits hit the memory hierarchy*:

* ``hamming_xor``  — bit-packed uint32 lanes, XOR + popcount on the VPU.
  32x less HBM traffic than any float representation; the memory-roofline
  winner for cardinality-bound scans. (This is the paper's "vector packing"
  insight, which failed on the AP for routability reasons but is a strict
  win here — see DESIGN.md.)
* ``hamming_mxu``  — +/-1 encoding, distance = (d - q.x)/2 via a bf16 matmul
  with f32 accumulation. Exact for d <= 2^24; turns the scan into systolic
  MXU work; the compute-roofline winner when codes are already resident.

Both agree bit-for-bit with ``hamming_ref``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WORD = 32


def padded_words(d: int) -> int:
    return (d + WORD - 1) // WORD


def pack_bits(bits: jax.Array) -> jax.Array:
    """bits: (..., d) in {0,1} -> packed (..., ceil(d/32)) uint32."""
    d = bits.shape[-1]
    W = padded_words(d)
    pad = W * WORD - d
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    b = bits.reshape(*bits.shape[:-1], W, WORD).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32))
    return jnp.sum(b * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(packed: jax.Array, d: int) -> jax.Array:
    """packed: (..., W) uint32 -> (..., d) uint8 in {0,1}."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*packed.shape[:-1], packed.shape[-1] * WORD)[..., :d].astype(jnp.uint8)


def _bit(packed: jax.Array, shift) -> jax.Array:
    """Bit ``shift`` of every 32-bit word, as int32 0/1."""
    u = packed if packed.dtype == jnp.uint32 else packed.astype(jnp.uint32)
    return (jax.lax.shift_right_logical(u, jnp.asarray(shift, jnp.uint32))
            & jnp.uint32(1)).astype(jnp.int32)


def bit_means(packed: jax.Array, d: int) -> jax.Array:
    """packed: (N, W) -> (d,) float32 fraction of rows with each bit set,
    counted word-parallel per bit offset (32 reductions over the packed
    array; nothing of size (N, d) is built)."""
    counts = jnp.stack([jnp.sum(_bit(packed, s), axis=0)
                        for s in range(WORD)], axis=1)          # (W, 32)
    return counts.reshape(-1)[:d].astype(jnp.float32) / packed.shape[0]


def bit_key(packed: jax.Array, positions: jax.Array) -> jax.Array:
    """packed: (N, W), positions: (b,) bit indices -> (N,) int32 key
    sum_i bit(positions[i]) << i, reading one word column per bit."""
    key = jnp.zeros(packed.shape[:1], jnp.int32)
    for i in range(positions.shape[0]):
        p = positions[i]
        word = jnp.take(packed, p // WORD, axis=1)
        key = key + (_bit(word, p % WORD) << i)
    return key


def hamming_ref(q_bits: jax.Array, x_bits: jax.Array) -> jax.Array:
    """Oracle: q_bits (Q, d), x_bits (N, d) in {0,1} -> (Q, N) int32."""
    diff = q_bits[:, None, :].astype(jnp.int32) != x_bits[None, :, :].astype(jnp.int32)
    return jnp.sum(diff, axis=-1, dtype=jnp.int32)


def hamming_xor(q_packed: jax.Array, x_packed: jax.Array) -> jax.Array:
    """Bit-packed XOR+popcount. q: (Q, W) uint32, x: (N, W) -> (Q, N) int32."""
    x = jax.lax.bitwise_xor(q_packed[:, None, :], x_packed[None, :, :])
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def hamming_mxu(q_bits: jax.Array, x_bits: jax.Array, d: int | None = None,
                dtype=jnp.bfloat16) -> jax.Array:
    """MXU path: distance = (d - <2q-1, 2x-1>) / 2, f32-accumulated matmul.

    q_bits: (Q, d), x_bits: (N, d) in {0,1} -> (Q, N) int32 (exact)."""
    d = d if d is not None else q_bits.shape[-1]
    qs = (2 * q_bits.astype(jnp.int8) - 1).astype(dtype)
    xs = (2 * x_bits.astype(jnp.int8) - 1).astype(dtype)
    dot = jax.lax.dot_general(qs, xs, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return ((d - dot) * 0.5).astype(jnp.int32)
