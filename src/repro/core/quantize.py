"""Binary quantization: ITQ (the paper's offline pipeline) + LSH codes.

ITQ (Gong & Lazebnik, CVPR'11): PCA to ``bits`` dims, then alternate
  B = sign(V R)          (discretize)
  R = U W^T  from  svd(V^T B) = U S W^T   (orthogonal Procrustes)
minimizing ||B - V R||_F over rotations.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class ITQParams(NamedTuple):
    mean: jax.Array       # (dim,)
    proj: jax.Array       # (dim, bits)  PCA
    rot: jax.Array        # (bits, bits) learned rotation


def itq_train(x: jax.Array, bits: int, iters: int = 30, key=None) -> ITQParams:
    """x: (n, dim) f32. Returns encode params."""
    key = key if key is not None else jax.random.PRNGKey(0)
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=0)
    xc = x - mean
    # PCA via SVD of the (dim, dim) covariance
    cov = (xc.T @ xc) / x.shape[0]
    _, _, vt = jnp.linalg.svd(cov, full_matrices=False)
    proj = vt[:bits].T                                        # (dim, bits)
    v = xc @ proj                                             # (n, bits)
    r0, _ = jnp.linalg.qr(jax.random.normal(key, (bits, bits), jnp.float32))

    def step(r, _):
        b = jnp.sign(v @ r)
        u, _, wt = jnp.linalg.svd(v.T @ b, full_matrices=False)
        return u @ wt, None

    rot, _ = jax.lax.scan(step, r0, None, length=iters)
    return ITQParams(mean=mean, proj=proj, rot=rot)


def itq_encode(x: jax.Array, p: ITQParams) -> jax.Array:
    """x: (..., dim) -> bits (..., code_bits) uint8 in {0,1}."""
    return (itq_project(x, p) > 0).astype(jnp.uint8)


def itq_project(x: jax.Array, p: ITQParams) -> jax.Array:
    """The CONTINUOUS rotated projection itq_encode signs: (..., dim) ->
    (..., code_bits) f32. The approx tier's asymmetric scoring path keeps
    queries at this float precision against the datastore's ±1 bit planes
    (kernels/approx_select.asymmetric_topk) — better ranking fidelity than
    query-side sign quantization at identical datastore bytes."""
    xc = x.astype(jnp.float32) - p.mean
    # a code bit is the sign of this projection, so it is computed at full
    # float32 precision (the TPU's default would round operands to bf16)
    hi = jax.lax.Precision.HIGHEST
    return jnp.matmul(jnp.matmul(xc, p.proj, precision=hi), p.rot,
                      precision=hi)


def itq_objective(x: jax.Array, p: ITQParams) -> jax.Array:
    """Quantization loss ||B - VR||_F^2 / n (monotone under training)."""
    v = (x.astype(jnp.float32) - p.mean) @ p.proj
    vr = v @ p.rot
    b = jnp.sign(vr)
    return jnp.mean(jnp.sum(jnp.square(b - vr), axis=-1))


class LSHParams(NamedTuple):
    proj: jax.Array       # (dim, bits) gaussian hyperplanes


def lsh_train(dim: int, bits: int, key=None) -> LSHParams:
    key = key if key is not None else jax.random.PRNGKey(0)
    return LSHParams(proj=jax.random.normal(key, (dim, bits), jnp.float32))


def lsh_encode(x: jax.Array, p: LSHParams) -> jax.Array:
    return (x.astype(jnp.float32) @ p.proj > 0).astype(jnp.uint8)
