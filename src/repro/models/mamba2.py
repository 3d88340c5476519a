"""Mamba2 (SSD) block — chunked parallel scan for train/prefill, O(1)-state
recurrent step for decode.

State-space recurrence per head h (P channels, N state):
    h_t = a_t * h_{t-1} + dt_t * (B_t outer x_t)      a_t = exp(dt_t * A_h), A_h < 0
    y_t = C_t . h_t + D_h * x_t

The chunked (SSD) algorithm computes, per chunk of length L:
  intra:  Y[t] += sum_{s<=t} (C_t . B_s) exp(l_t - l_s) dt_s x_s
  inter:  Y[t] += exp(l_t) * (C_t . h_in)
  carry:  h_out = exp(l_L) h_in + sum_s exp(l_L - l_s) dt_s (B_s outer x_s)
with l_t the within-chunk cumulative log-decay (computed in f32; every
exponent is <= 0 so the exps are stable).

Projections are kept as separate matrices (w_z/w_x/w_b/w_c/w_dt rather than
one fused in-proj) so each can carry its own TP PartitionSpec with shard
boundaries aligned to its semantic dimension; the depthwise conv is likewise
split per stream (identical math — depthwise convs commute with concat).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, dense_init, rmsnorm, rmsnorm_init


class MambaState(NamedTuple):
    ssm: jax.Array        # (B, H, P, N) f32
    conv_x: jax.Array     # (B, W-1, d_inner) rolling raw inputs
    conv_b: jax.Array     # (B, W-1, N)
    conv_c: jax.Array     # (B, W-1, N)


def _dims(cfg: ModelConfig):
    ssm = cfg.ssm
    d_inner = ssm.expand * cfg.d_model
    n_heads = d_inner // ssm.head_dim
    return d_inner, n_heads


def mamba2_init(key, cfg: ModelConfig, dtype) -> Params:
    ssm = cfg.ssm
    d_inner, n_heads = _dims(cfg)
    ks = jax.random.split(key, 9)
    conv_init = lambda k, c: (jax.random.normal(k, (ssm.conv_width, c), jnp.float32)
                              * ssm.conv_width ** -0.5).astype(dtype)
    return {
        "w_z": dense_init(ks[0], cfg.d_model, d_inner, dtype),
        "w_x": dense_init(ks[1], cfg.d_model, d_inner, dtype),
        "w_b": dense_init(ks[2], cfg.d_model, ssm.state_dim, dtype),
        "w_c": dense_init(ks[3], cfg.d_model, ssm.state_dim, dtype),
        "w_dt": dense_init(ks[4], cfg.d_model, n_heads, dtype),
        "conv_x": conv_init(ks[5], d_inner),
        "conv_b": conv_init(ks[6], ssm.state_dim),
        "conv_c": conv_init(ks[7], ssm.state_dim),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads, dtype=jnp.float32)),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "norm": rmsnorm_init(d_inner),
        "w_out": dense_init(ks[8], d_inner, cfg.d_model, dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, state=None, lengths=None):
    """Depthwise causal conv via shifted adds + silu. x: (B, S, C); w: (W, C).

    ``state``: (B, W-1, C) past raw inputs (decode). Returns (y, new_state);
    with ``lengths`` (B,) the new state holds the W-1 inputs before each
    row's own length (right padding)."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                    # (B, S+W-1, C)
    S = x.shape[1]
    y = sum(xp[:, i:i + S, :] * w[i] for i in range(W))
    y = jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype)
    if lengths is not None:
        return y, jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
            r, n, W - 1, axis=0))(xp, lengths)
    return y, xp[:, -(W - 1):, :]


def _ssd_chunked(x, b_mat, c_mat, dt, a_log, chunk: int, h0=None):
    """x: (B,S,H,P); b_mat/c_mat: (B,S,N); dt: (B,S,H) f32; ``h0`` the
    carried (B,H,P,N) state (zeros when None).

    Returns (y (B,S,H,P) f32, h_final (B,H,P,N) f32)."""
    B, S, H, P = x.shape
    N = b_mat.shape[-1]
    L = min(chunk, S)
    S_pad = ((S + L - 1) // L) * L
    if S_pad != S:
        # pad with inert steps: x=0 (no contribution), dt=0 => decay exp(0)=1
        # (state preserved), so the returned state is exact.
        pz = lambda a: jnp.pad(a, [(0, 0), (0, S_pad - S)] + [(0, 0)] * (a.ndim - 2))
        x, b_mat, c_mat, dt = pz(x), pz(b_mat), pz(c_mat), pz(dt)
    S_orig, S = S, S_pad
    nc = S // L

    a = -jnp.exp(a_log)                                       # (H,) negative
    loga_step = dt * a                                        # (B,S,H) <= 0
    xf = x.astype(jnp.float32)
    bf = b_mat.astype(jnp.float32)
    cf = c_mat.astype(jnp.float32)

    def r(t):  # reshape into chunks
        return t.reshape(t.shape[0], nc, L, *t.shape[2:])

    xc, bc, cc = r(xf), r(bf), r(cf)
    dtc, logc = r(dt), r(loga_step)

    def body(h, inp):
        x_l, b_l, c_l, dt_l, lg = inp                         # (B,L,...)
        l_cum = jnp.cumsum(lg, axis=1)                        # (B,L,H)
        # intra-chunk
        cb = jnp.einsum("bln,bsn->bls", c_l, b_l)             # (B,L,L)
        diff = l_cum[:, :, None, :] - l_cum[:, None, :, :]    # (B,L,L,H) t,s
        mask = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])
        decay = jnp.where(mask[None, :, :, None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        scores = cb[:, :, :, None] * decay                    # (B,L,L,H)
        dtx = dt_l[..., None] * x_l                           # (B,L,H,P)
        y = jnp.einsum("blsh,bshp->blhp", scores, dtx)
        # inter-chunk (carried state)
        y += jnp.exp(l_cum)[..., None] * jnp.einsum("bln,bhpn->blhp", c_l, h)
        # state update
        rem = jnp.exp(l_cum[:, -1:, :] - l_cum)               # (B,L,H)
        h_new = jnp.exp(l_cum[:, -1])[:, :, None, None] * h + jnp.einsum(
            "bsh,bsn,bshp->bhpn", rem * dt_l, b_l, x_l)
        return h_new, y

    h0 = jnp.zeros((B, H, P, N), jnp.float32) if h0 is None else h0
    h_final, yc = jax.lax.scan(
        body, h0,
        (xc.swapaxes(0, 1), bc.swapaxes(0, 1), cc.swapaxes(0, 1),
         dtc.swapaxes(0, 1), logc.swapaxes(0, 1)))
    y = yc.swapaxes(0, 1).reshape(B, S, H, P)[:, :S_orig]
    return y, h_final


def _projections(params: Params, x: jax.Array, state: MambaState | None,
                 lengths=None):
    z = x @ params["w_z"]
    x_in = x @ params["w_x"]
    b_in = x @ params["w_b"]
    c_in = x @ params["w_c"]
    dt = x @ params["w_dt"]
    sx = None if state is None else state.conv_x
    sb = None if state is None else state.conv_b
    sc = None if state is None else state.conv_c
    x_ssm, nx = _causal_conv(x_in, params["conv_x"], sx, lengths)
    b_mat, nb = _causal_conv(b_in, params["conv_b"], sb, lengths)
    c_mat, nc = _causal_conv(c_in, params["conv_c"], sc, lengths)
    return z, x_ssm, b_mat, c_mat, dt, (nx, nb, nc)


def mamba2_forward(params: Params, cfg: ModelConfig, x: jax.Array,
                   return_state: bool = False, lengths=None,
                   state0: MambaState | None = None):
    """Full-sequence Mamba2 block. x: (B, S, d_model), from ``state0``
    (zeros when None). With ``lengths`` (B,) the rows are right-padded:
    padded steps get dt = 0 (no input, decay 1), so the returned state is
    each row's state after its own length."""
    ssm = cfg.ssm
    d_inner, n_heads = _dims(cfg)
    B, S, _ = x.shape
    z, x_ssm, b_mat, c_mat, dt, conv_states = _projections(params, x, state0,
                                                           lengths)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    if lengths is not None:
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)
    xh = x_ssm.reshape(B, S, n_heads, ssm.head_dim)
    y, h = _ssd_chunked(xh, b_mat, c_mat, dt, params["a_log"], ssm.chunk_size,
                        None if state0 is None else state0.ssm)
    y = y + params["d_skip"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ params["w_out"]
    if return_state:
        nx, nb, nc = conv_states
        return out, MambaState(ssm=h, conv_x=nx, conv_b=nb, conv_c=nc)
    return out


def mamba2_step(params: Params, cfg: ModelConfig, x: jax.Array, state: MambaState):
    """Single-token decode. x: (B, 1, d_model) -> (y, new_state)."""
    ssm = cfg.ssm
    d_inner, n_heads = _dims(cfg)
    B = x.shape[0]
    z, x_ssm, b_mat, c_mat, dt, conv_states = _projections(params, x, state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]  # (B,H)
    xh = x_ssm.reshape(B, n_heads, ssm.head_dim).astype(jnp.float32)
    bf = b_mat[:, 0].astype(jnp.float32)                      # (B,N)
    cf = c_mat[:, 0].astype(jnp.float32)
    a_step = jnp.exp(dt * -jnp.exp(params["a_log"]))          # (B,H)
    h = state.ssm * a_step[..., None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt, bf, xh)
    y = jnp.einsum("bn,bhpn->bhp", cf, h) + params["d_skip"][None, :, None] * xh
    y = y.reshape(B, 1, d_inner).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    nx, nb, nc = conv_states
    return y @ params["w_out"], MambaState(ssm=h, conv_x=nx, conv_b=nb, conv_c=nc)


def init_mamba_state(cfg: ModelConfig, batch: int) -> MambaState:
    ssm = cfg.ssm
    d_inner, n_heads = _dims(cfg)
    dt = jnp.dtype(cfg.dtype)
    w1 = ssm.conv_width - 1
    return MambaState(
        ssm=jnp.zeros((batch, n_heads, ssm.head_dim, ssm.state_dim), jnp.float32),
        conv_x=jnp.zeros((batch, w1, d_inner), dt),
        conv_b=jnp.zeros((batch, w1, ssm.state_dim), dt),
        conv_c=jnp.zeros((batch, w1, ssm.state_dim), dt),
    )
