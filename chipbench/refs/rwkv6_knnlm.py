"""The plain float32 reference of kNN-LM serving on RWKV-6 "Finch" 1.6B,
written from the published equations (Peng et al., arXiv:2404.05892;
RWKV-LM ``v6`` ``RWKV_Tmix_x060`` / ``RWKV_CMix_x060``) in jax.numpy, with
every matmul at ``highest`` precision.

The LM runs from a zero state one token at a time through the recurrence,
with no cache and no kernels. To fit beside the freed program it computes
in blocks: the checked sequences side by side as the rows of one step, the
weights upcast one layer at a time inside the step, and the logits only at
the positions asked for. Its weights are the benchmark's own
(``init_params``: drawn from the seed at the configuration's published
shapes, then loaded into the program), read by name from the tree:

    embed.table, ln0, blocks.{ln1, att, ln2, ffn} (stacked over layers),
    final_norm (ln_out), unembed.table

So are the ITQ tables (``fit_itq``: PCA and ITQ rotations on the host over
hidden states sampled from the served model).

Block (x: the residual stream; LayerNorms with bias, eps 1e-5):

    h = ln1(x); xx = shift(h) - h
    m = tanh((h + xx maa_x) maa_w1) maa_w2          five rank-32 mixes
    x_c = h + xx (maa_c + m_c)      for c in (w, k, v, r, g)
    r, k, v = x_r W_r, x_k W_k, x_v W_v;  g = silu(x_g W_g)
    w = exp(-exp(decay + tanh(x_w decay_w1) decay_w2))
    per head: y = r (S + u k^T v);  S = diag(w) S + k^T v
    x = x + (GroupNorm_heads(y; eps 64e-5) * g) W_o
    h = ln2(x); xx = shift(h) - h
    x = x + sigmoid((h + xx maa_r) W_r') * (relu((h + xx maa_k) W_k')^2 W_v')

``shift`` is the previous token's value (zeros before the first token).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
GROUP_NORM_EPS_SCALE = 64.0          # head_size_divisor 8, squared


def param_shapes(config: dict) -> dict:
    """The Finch parameter tree at the configuration's published widths:
    {dotted name: (shape, dtype)}. Block leaves are stacked over layers;
    matrices in the configuration's dtype, per-channel vectors float32."""
    L, d = config["num_hidden_layers"], config["hidden_size"]
    f, V = config["intermediate_size"], config["vocab_size"]
    hd = config["head_size"]
    mix, dec = config["time_mix_extra_dim"], config["time_decay_extra_dim"]
    mat, vec = jnp.dtype(config["dtype"]), jnp.dtype(jnp.float32)
    norm = lambda pre, *lead: {f"{pre}.scale": (lead + (d,), vec),
                               f"{pre}.bias": (lead + (d,), vec)}
    out = {"embed.table": ((V, d), mat), **norm("ln0"),
           **norm("final_norm"), "unembed.table": ((V, d), mat),
           **norm("blocks.ln1", L), **norm("blocks.ln2", L),
           **norm("blocks.att.ln_x", L)}
    att = {"maa_x": ((d,), vec), "maa_wkvrg": ((5, d), vec),
           "maa_w1": ((d, 5 * mix), mat), "maa_w2": ((5, mix, d), mat),
           "decay": ((d,), vec), "decay_w1": ((d, dec), mat),
           "decay_w2": ((dec, d), mat), "bonus": ((d // hd, hd), vec),
           **{w: ((d, d), mat) for w in ("w_r", "w_k", "w_v", "w_g", "w_o")}}
    ffn = {"maa_k": ((d,), vec), "maa_r": ((d,), vec),
           "w_k": ((d, f), mat), "w_v": ((f, d), mat), "w_r": ((d, d), mat)}
    for pre, group in (("blocks.att", att), ("blocks.ffn", ffn)):
        out.update({f"{pre}.{n}": ((L,) + shape, dt)
                    for n, (shape, dt) in group.items()})
    return out


def _draw(key, name: str, shape, dtype):
    """One leaf: matrices normal with standard deviation fan_in^-0.5 (the
    two LoRA up-projections 0.1, the embedding and head tables 0.02);
    token-shift mixes U(0, 1); decay base U(-6, -1) (decays exp(-exp(.))
    from 0.69 to 0.998 a token before the LoRA term); bonus U(-0.5, 0.5);
    norm scales 1 + 0.1 N(0, 1), biases 0.1 N(0, 1)."""
    leaf = name.rsplit(".", 1)[-1]
    u = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    n = lambda std: jax.random.normal(key, shape, jnp.float32) * std
    if leaf.startswith("maa_") and leaf not in ("maa_w1", "maa_w2"):
        a = u(0.0, 1.0)
    elif leaf == "decay":
        a = u(-6.0, -1.0)
    elif leaf == "bonus":
        a = u(-0.5, 0.5)
    elif leaf == "scale":
        a = 1.0 + n(0.1)
    elif leaf == "bias":
        a = n(0.1)
    elif leaf == "table":
        a = n(0.02)
    elif leaf in ("maa_w2", "decay_w2"):
        a = n(0.1)
    else:                                     # (in, out), maybe stacked
        a = n(shape[-2] ** -0.5)
    return a.astype(dtype)


def init_params(key, config: dict) -> dict:
    """The benchmark's Finch weights, drawn from ``key`` at the published
    shapes (``param_shapes``), as a nested dict named like the program's
    parameter tree."""
    tree: dict = {}
    for i, (name, (shape, dtype)) in enumerate(
            sorted(param_shapes(config).items())):
        *path, leaf = name.split(".")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = _draw(jax.random.fold_in(key, i), name, shape, dtype)
    return tree


def fit_itq(sample, bits: int, iters: int, key):
    """ITQ (Gong and Lazebnik) on ``sample`` (n, d) float32 hidden states:
    the mean, the top-``bits`` PCA directions (float64 eigendecomposition
    of the covariance) and ``iters`` alternations of B = sign(V R) and R
    the orthogonal polar factor of V^T B, from a seeded orthogonal R.
    Returns (mean (d,), proj (d, bits), rot (bits, bits)), float32."""
    x = jnp.asarray(sample, jnp.float32)
    mean = jnp.mean(x, axis=0)
    xc = x - mean
    cov = np.asarray(jnp.matmul(xc.T, xc, precision=HI), np.float64)
    _, vecs = np.linalg.eigh(cov)
    proj = np.ascontiguousarray(vecs[:, ::-1][:, :bits], np.float32)
    v = np.asarray(jnp.matmul(xc, proj, precision=HI), np.float64)
    rot, _ = np.linalg.qr(np.asarray(
        jax.random.normal(key, (bits, bits), jnp.float32), np.float64))
    for _ in range(iters):
        u, _, wt = np.linalg.svd(v.T @ np.sign(v @ rot))
        rot = u @ wt
    return (mean, jnp.asarray(proj), jnp.asarray(rot, jnp.float32))


def _mm(a, b):
    return jnp.matmul(a, jnp.asarray(b, jnp.float32), precision=HI)


def _ln(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _block(p, x, st, *, heads: int, eps: float):
    """One block for one token of R rows. x: (R, d); st: (wkv (R,H,hd,hd),
    previous ln1 output (R, d), previous ln2 output (R, d))."""
    wkv, prev_t, prev_c = st
    a, f = p["att"], p["ffn"]
    R, d = x.shape
    hd = d // heads

    h = _ln(p["ln1"], x, eps)
    xx = prev_t - h
    m = jnp.tanh(_mm(h + xx * a["maa_x"], a["maa_w1"])).reshape(R, 5, -1)
    m = jnp.einsum("rcm,cmd->crd", m, jnp.asarray(a["maa_w2"], jnp.float32),
                   precision=HI)
    xw, xk, xv, xr, xg = [h + xx * (a["maa_wkvrg"][c] + m[c])
                          for c in range(5)]
    r = _mm(xr, a["w_r"]).reshape(R, heads, hd)
    k = _mm(xk, a["w_k"]).reshape(R, heads, hd)
    v = _mm(xv, a["w_v"]).reshape(R, heads, hd)
    g = jax.nn.silu(_mm(xg, a["w_g"]))
    w = jnp.exp(-jnp.exp(a["decay"] + _mm(jnp.tanh(_mm(xw, a["decay_w1"])),
                                          a["decay_w2"])))
    w = w.reshape(R, heads, hd)
    kv = k[..., :, None] * v[..., None, :]                     # (R,H,i,j)
    y = jnp.einsum("rhi,rhij->rhj", r, wkv + a["bonus"][..., None] * kv,
                   precision=HI)
    wkv = w[..., None] * wkv + kv
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), axis=-1, keepdims=True)
    y = ((y - mu) / jnp.sqrt(var + eps * GROUP_NORM_EPS_SCALE)).reshape(R, d)
    y = y * a["ln_x"]["scale"] + a["ln_x"]["bias"]
    x = x + _mm(y * g, a["w_o"])

    h2 = _ln(p["ln2"], x, eps)
    xx = prev_c - h2
    kk = jnp.square(jax.nn.relu(_mm(h2 + xx * f["maa_k"], f["w_k"])))
    x = x + jax.nn.sigmoid(_mm(h2 + xx * f["maa_r"], f["w_r"])) * _mm(
        kk, f["w_v"])
    return x, (wkv, h, h2)


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _hidden(params, tokens, *, heads: int, eps: float):
    R, T = tokens.shape
    blocks = params["blocks"]
    L = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    d = params["embed"]["table"].shape[1]
    hd = d // heads

    def token_step(states, tok):
        x = _ln(params["ln0"], jnp.asarray(params["embed"]["table"][tok],
                                           jnp.float32), eps)

        def layer_step(x, ps):
            p_l, st = ps
            return _block(p_l, x, st, heads=heads, eps=eps)

        x, states = jax.lax.scan(layer_step, x, (blocks, states))
        return states, _ln(params["final_norm"], x, eps)

    zeros = (jnp.zeros((L, R, heads, hd, hd), jnp.float32),
             jnp.zeros((L, R, d), jnp.float32),
             jnp.zeros((L, R, d), jnp.float32))
    _, h = jax.lax.scan(token_step, zeros, tokens.T)
    return h.swapaxes(0, 1)                                    # (R, T, d)


def lm_hidden(params, config: dict, tokens):
    """tokens: (R, T) int32, each row its own sequence (pad at the end: a
    position's output depends on earlier tokens only) -> (R, T, d) float32
    hidden states after ln_out, from a zero state."""
    return _hidden(params, jnp.asarray(tokens, jnp.int32),
                   heads=config["hidden_size"] // config["head_size"],
                   eps=float(config["layer_norm_epsilon"]))


@jax.jit
def lm_log_softmax(params, hidden):
    """hidden: (n, d) float32 -> the LM's log-softmax (n, V), float32."""
    logits = jnp.matmul(hidden, jnp.asarray(params["unembed"]["table"],
                                            jnp.float32).T, precision=HI)
    return jax.nn.log_softmax(logits, axis=-1)


@jax.jit
def itq_project(keys, mean, proj, rot):
    """The continuous ITQ projection of keys (n, d), float32: a code bit is
    its sign. Also returns each entry's float32 accumulation margin,
    2 (d + bits) 2^-24 (|key - mean| |proj| |rot|): twice the classical
    bound on the rounding of both products, so one float32 evaluation of a
    projection beyond it has the sign of the exact one."""
    xc = jnp.asarray(keys, jnp.float32) - mean
    v = jnp.matmul(jnp.matmul(xc, proj, precision=HI), rot, precision=HI)
    bound = jnp.matmul(jnp.matmul(jnp.abs(xc), jnp.abs(proj), precision=HI),
                       jnp.abs(rot), precision=HI)
    n = proj.shape[0] + proj.shape[1]
    return v, 2.0 * n * 2.0 ** -24 * bound


def unpack_bits(codes, bits: int):
    """(n, bits/32) uint32 words -> (n, bits) bool; bit i of a code is bit
    i % 32 of word i // 32."""
    w = jnp.asarray(codes, jnp.uint32)[..., None] >> jnp.arange(
        32, dtype=jnp.uint32)
    return ((w & 1) == 1).reshape(*codes.shape[:-1], bits)


@functools.partial(jax.jit, static_argnames=("vocab",))
def knn_log_probs(dists, ids, values, temperature, *, vocab: int):
    """The kNN-LM neighbour distribution of (dists, ids) (n, k): softmax of
    -distance / temperature, summed per stored next token; log, floor
    1e-9."""
    w = jax.nn.softmax(-jnp.asarray(dists, jnp.float32) / temperature,
                       axis=-1)
    n = dists.shape[0]
    p = jnp.zeros((n, vocab), jnp.float32).at[
        jnp.arange(n)[:, None], values[ids]].add(w)
    return jnp.log(jnp.maximum(p, 1e-9))


@jax.jit
def interpolate(lm_logits, knn_logp, lam):
    """log((1 - lam) softmax(lm_logits) + lam exp(knn_logp)), float32."""
    lm = jax.nn.log_softmax(jnp.asarray(lm_logits, jnp.float32), axis=-1)
    return jnp.logaddexp(lm + jnp.log1p(-lam), knn_logp + jnp.log(lam))


def block_out(p, x, wkv, shift_t, shift_c, config: dict):
    """One block (``p``: a layer's weights) for rows of one token each,
    from given inputs: x, shift_t, shift_c (n, d) and wkv (n, H, hd, hd).
    Returns the block's output (n, d), float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    out, _ = _block_jit(p, f32(x), (f32(wkv), f32(shift_t), f32(shift_c)),
                        heads=config["hidden_size"] // config["head_size"],
                        eps=float(config["layer_norm_epsilon"]))
    return out


_block_jit = jax.jit(_block, static_argnames=("heads", "eps"))


@jax.jit
def recurrence_err(r, k, v, logw, y, bonus, s_in, s_next, has_next):
    """The WKV recurrence of one layer recomputed in float32 from given
    inputs, rows of one token each: r, k, v, logw, y (n, d); ``s_in`` the
    state the step read and ``s_next`` the state the row's next step read
    (n, H, hd, hd), where ``has_next`` (n,). Returns, per row, the largest
    error of y and of the next state (0 without one), each entry over the
    sum of the magnitudes of its terms (so a float32 evaluation reads a few
    2^-24 at most):

        y[j] = sum_i r[i] (s_in[i,j] + u[i] k[i] v[j])
        s_next[i,j] = exp(logw[i]) s_in[i,j] + k[i] v[j]
    """
    H, hd = s_in.shape[1:3]
    sh = lambda a: jnp.asarray(a, jnp.float32).reshape(-1, H, hd)
    r, k, v, w, y = sh(r), sh(k), sh(v), jnp.exp(sh(logw)), sh(y)
    kv = k[..., :, None] * v[..., None, :]
    ukv = bonus[None, :, :, None] * kv
    y_ref = jnp.einsum("nhi,nhij->nhj", r, s_in + ukv, precision=HI)
    y_mag = jnp.einsum("nhi,nhij->nhj", jnp.abs(r),
                       jnp.abs(s_in) + jnp.abs(ukv), precision=HI)
    s_ref = w[..., None] * s_in + kv
    s_mag = w[..., None] * jnp.abs(s_in) + jnp.abs(kv)
    tiny = jnp.finfo(jnp.float32).tiny
    y_err = jnp.max(jnp.abs(y - y_ref) / (y_mag + tiny), axis=(1, 2))
    s_err = jnp.max(jnp.abs(s_next - s_ref) / (s_mag + tiny), axis=(1, 2, 3))
    return y_err, jnp.where(has_next, s_err, 0.0)
