"""Foundational model layers as pure functions over dict pytrees.

Every layer has an ``init_*`` returning a param pytree and an ``apply``-style
function. No framework (flax/haiku) — plain pytrees keep pjit shardings and
scan-stacking explicit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = dict


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else in_dim ** -0.5
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(dt)


# ---------------------------------------------------------------------------
# LayerNorm (with bias)
# ---------------------------------------------------------------------------

def layernorm_init(dim: int, key=None) -> Params:
    """Unit scale and zero bias; with ``key``, both drawn about those
    values (scale 1 + 0.1 N(0, 1), bias 0.1 N(0, 1)), so a seeded model
    exercises the affine."""
    if key is None:
        return {"scale": jnp.ones((dim,), jnp.float32),
                "bias": jnp.zeros((dim,), jnp.float32)}
    ks, kb = jax.random.split(key)
    return {"scale": 1.0 + 0.1 * jax.random.normal(ks, (dim,), jnp.float32),
            "bias": 0.1 * jax.random.normal(kb, (dim,), jnp.float32)}


def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Normalize over the last axis in float32, then scale and bias; the
    result keeps ``x``'s dtype."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                      # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (swiglu / geglu / gelu / relu_sq)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, activation: str, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_out": dense_init(k2, d_ff, d_model, dtype)}
    if activation in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(k1, d_model, d_ff, dtype)
        p["w_up"] = dense_init(k3, d_model, d_ff, dtype)
    else:
        p["w_up"] = dense_init(k1, d_model, d_ff, dtype)
    return p


def mlp(params: Params, x: jax.Array, activation: str) -> jax.Array:
    if activation == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    elif activation == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"], approximate=True) * (x @ params["w_up"])
    elif activation == "gelu":
        h = jax.nn.gelu(x @ params["w_up"], approximate=True)
    elif activation == "relu_sq":
        h = jnp.square(jax.nn.relu(x @ params["w_up"]))
    else:
        raise ValueError(f"unknown activation {activation!r}")
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, d_model: int, dtype) -> Params:
    return {"table": (jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02).astype(dtype)}


def embed(params: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params: Params, x: jax.Array) -> jax.Array:
    """Logits via the (possibly tied) output table: (..., d) -> (..., vocab)."""
    return x @ params["table"].T


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None):
    """Mean token-level cross entropy; logits (..., V) may be vocab-sharded
    (logsumexp reduces over the sharded axis; SPMD inserts the collective)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
