"""The plain float32 reference of kNN-LM serving on RWKV-6 "Finch": one
sequence, one token at a time through the recurrence, no cache, batching or
kernels, every matmul at ``highest`` precision.

- ``forward(params, cfg, tokens)``: the Finch LM (the equations of
  ``models/rwkv6.py``'s docstring) from a zero state over ``tokens``;
  returns each position's hidden state after ``ln_out`` (the retrieval key)
  and its logits. The weights are the system's own parameters, upcast.
- ``encode``: the ITQ sign code of a key, packed 32 bits a word.
- ``knn``: exact Hamming k-NN by a full scan (``kernels/ref.py``), ties by
  row index.
- ``knn_log_probs`` and ``interpolate``: the kNN-LM neighbour distribution
  (softmax of -distance / temperature, summed per value) and its mix with
  the LM's softmax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref

LN_EPS_SCALE_X = 64.0      # ln_x eps over the LayerNorm eps


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _ln(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _layer(p, cfg, x, st):
    """One block, one token. x: (d,); st: (wkv (H,hd,hd), xt (d,), xc (d,))."""
    eps = cfg.norm_eps
    hd = cfg.rwkv.head_dim
    H = cfg.d_model // hd
    wkv, xt_prev, xc_prev = st
    a, f = p["att"], p["ffn"]

    h = _ln(p["ln1"], x, eps)
    xx = xt_prev - h
    m = jnp.tanh((h + xx * a["maa_x"]) @ a["maa_w1"]).reshape(5, -1)
    m = jnp.einsum("fr,frd->fd", m, a["maa_w2"])
    xw, xk, xv, xr, xg = [h + xx * (a["maa_wkvrg"][i] + m[i])
                          for i in range(5)]
    r = (xr @ a["w_r"]).reshape(H, hd)
    k = (xk @ a["w_k"]).reshape(H, hd)
    v = (xv @ a["w_v"]).reshape(H, hd)
    g = jax.nn.silu(xg @ a["w_g"])
    w = jnp.exp(-jnp.exp(a["decay"] + jnp.tanh(xw @ a["decay_w1"])
                         @ a["decay_w2"])).reshape(H, hd)
    kv = k[:, :, None] * v[:, None, :]                         # (H, i, j)
    y = jnp.einsum("hi,hij->hj", r, wkv + a["bonus"][:, :, None] * kv)
    wkv = w[:, :, None] * wkv + kv
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), axis=-1, keepdims=True)
    y = ((y - mu) / jnp.sqrt(var + eps * LN_EPS_SCALE_X)).reshape(-1)
    y = y * a["ln_x"]["scale"] + a["ln_x"]["bias"]
    x = x + (y * g) @ a["w_o"]

    h2 = _ln(p["ln2"], x, eps)
    xx = xc_prev - h2
    kk = jnp.square(jax.nn.relu((h2 + xx * f["maa_k"]) @ f["w_k"]))
    x = x + jax.nn.sigmoid((h2 + xx * f["maa_r"]) @ f["w_r"]) * (kk @ f["w_v"])
    return x, (wkv, h, h2)


def forward(params, cfg, tokens):
    """tokens: (S,) int -> (hidden (S, d), logits (S, V)), float32, from a
    zero state."""
    with jax.default_matmul_precision("highest"):
        return _forward(_f32(params), cfg, jnp.asarray(tokens, jnp.int32))


def _forward(p, cfg, tokens):
    L, d = cfg.num_layers, cfg.d_model
    hd = cfg.rwkv.head_dim
    H = d // hd

    def token_step(states, tok):
        x = _ln(p["ln0"], p["embed"]["table"][tok], cfg.norm_eps)

        def layer_step(x, ps):
            p_l, st = ps
            x, st = _layer(p_l, cfg, x, st)
            return x, st

        x, states = jax.lax.scan(layer_step, x, (p["blocks"], states))
        h = _ln(p["final_norm"], x, cfg.norm_eps)
        return states, (h, h @ p["unembed"]["table"].T)

    zeros = (jnp.zeros((L, H, hd, hd), jnp.float32),
             jnp.zeros((L, d), jnp.float32), jnp.zeros((L, d), jnp.float32))
    _, (hidden, logits) = jax.lax.scan(token_step, zeros, tokens)
    return hidden, logits


def encode(hidden, itq):
    """(..., d) keys -> (..., bits/32) uint32 ITQ codes, float32 highest."""
    with jax.default_matmul_precision("highest"):
        proj = (jnp.asarray(hidden, jnp.float32) - itq.mean) @ itq.proj \
            @ itq.rot
    bits = (proj > 0).astype(jnp.uint32)
    bits = bits.reshape(*bits.shape[:-1], -1, 32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def knn(codes, queries, k: int):
    """Exact Hamming k-NN by a full scan: (dists, ids), each (Q, k) int32,
    ascending distance, ties by row index."""
    dist = kref.hamming_distance_ref(queries, codes)            # (Q, N)
    n = codes.shape[0]
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), dist.shape)
    dd, ii = jax.lax.sort((dist, ids), num_keys=2)
    return dd[:, :k], ii[:, :k]


def knn_log_probs(dists, ids, values, vocab: int, temperature: float = 8.0):
    """log of the neighbour distribution over the vocabulary (floor 1e-9)."""
    w = jax.nn.softmax(-dists.astype(jnp.float32) / temperature, axis=-1)
    p = jnp.zeros((dists.shape[0], vocab), jnp.float32)
    p = p.at[jnp.arange(dists.shape[0])[:, None], values[ids]].add(w)
    return jnp.log(jnp.maximum(p, 1e-9))


def interpolate(lm_logits, knn_logp, lam: float):
    """log((1 - lam) softmax(lm_logits) + lam exp(knn_logp))."""
    lm = jax.nn.log_softmax(jnp.asarray(lm_logits, jnp.float32), axis=-1)
    return jnp.logaddexp(lm + jnp.log1p(-lam), knn_logp + jnp.log(lam))
