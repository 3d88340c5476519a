"""RWKV-6 "Finch" block (Peng et al., arXiv:2404.05892; RWKV-LM ``v6``
``RWKV_Tmix_x060`` / ``RWKV_CMix_x060``).

    x = x + time_mix(ln1(x));  x = x + channel_mix(ln2(x))

Time mix. Token shift is data dependent ("ddlerp"): with xx = shift(x) - x,
    m_* = tanh((x + xx * maa_x) @ maa_w1) @ maa_w2[*]       (* in w, k, v, r, g)
    x_* = x + xx * (maa_* + m_*)
Then r, k, v = x_r W_r, x_k W_k, x_v W_v; g = silu(x_g W_g); the decay is
    w_t = exp(-exp(decay + tanh(x_w @ decay_w1) @ decay_w2))  in (0, 1)
and per head (hd key channels i, hd value channels j)
    y_t[j]   = sum_i r_t[i] * (S_{t-1}[i,j] + u[i] k_t[i] v_t[j])
    S_t[i,j] = w_t[i] * S_{t-1}[i,j] + k_t[i] v_t[j]
followed by a per-head GroupNorm ``ln_x`` (weight and bias, eps
64e-5 = 1e-5 x head_size_divisor^2), the gate and W_o.

Channel mix: x_k, x_r = x + xx * maa_k, x + xx * maa_r;
    out = sigmoid(x_r W_r) * (relu(x_k W_k)^2 W_v).

Precision: weights and activations in the configuration's dtype (bf16);
the WKV state, the decay and the GroupNorm in float32. The recurrence's
contractions run at ``highest`` precision: the TPU's default would round
their float32 operands (the state among them) to bf16.

Prefill evaluates the WKV in chunks: within a chunk the contribution of step
s to step t > s decays by exp(Lc[t-1] - Lc[s]) per channel (Lc = cumulative
log decay); the per-channel decay tensor is materialized (every exponent
<= 0, so exact and stable) and contracted, and the carried state handles
chunk boundaries. Decode (one token) is the O(1)-state recurrence.

Right-padded batches pass ``valid`` (B, S): padded steps get k = 0 and
log-decay 0, so they leave the WKV state as it was, and the shift states
are the last valid token's, so the returned state is the state after each
row's own length.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, dense_init, layernorm, layernorm_init

# ``ln_x`` eps over the LayerNorm eps: head_size_divisor (8) squared
LN_X_EPS_SCALE = 64.0
WKV_CHUNK = 32
HI = jax.lax.Precision.HIGHEST


class RWKVState(NamedTuple):
    wkv: jax.Array        # (B, H, hd, hd) f32
    shift_t: jax.Array    # (B, d) last token's ln1 output (time-mix shift)
    shift_c: jax.Array    # (B, d) last token's ln2 output (channel-mix shift)


def _dims(cfg: ModelConfig):
    hd = cfg.rwkv.head_dim
    return cfg.d_model // hd, hd


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def rwkv6_block_init(key, cfg: ModelConfig, dtype) -> Params:
    """One Finch block, drawn from ``key``: matrices in ``dtype``, the
    per-channel vectors (token-shift mixes, decay base, bonus, norm affine)
    in float32."""
    d, f = cfg.d_model, cfg.d_ff
    n_heads, hd = _dims(cfg)
    mix, dec = cfg.rwkv.mix_lora, cfg.rwkv.decay_lora
    ks = jax.random.split(key, 20)
    u01 = lambda k, shape: jax.random.uniform(k, shape, jnp.float32)
    att = {
        "maa_x": u01(ks[0], (d,)),
        "maa_wkvrg": u01(ks[1], (5, d)),
        "maa_w1": _normal(ks[2], (d, 5 * mix), d ** -0.5, dtype),
        "maa_w2": _normal(ks[3], (5, mix, d), 0.1, dtype),
        "decay": jax.random.uniform(ks[4], (d,), jnp.float32, -6.0, -1.0),
        "decay_w1": _normal(ks[5], (d, dec), d ** -0.5, dtype),
        "decay_w2": _normal(ks[6], (dec, d), 0.1, dtype),
        "bonus": jax.random.uniform(ks[7], (n_heads, hd), jnp.float32,
                                    -0.5, 0.5),
        "w_r": dense_init(ks[8], d, d, dtype),
        "w_k": dense_init(ks[9], d, d, dtype),
        "w_v": dense_init(ks[10], d, d, dtype),
        "w_g": dense_init(ks[11], d, d, dtype),
        "w_o": dense_init(ks[12], d, d, dtype),
        "ln_x": layernorm_init(d, ks[17]),
    }
    ffn = {
        "maa_k": u01(ks[13], (d,)),
        "maa_r": u01(ks[14], (d,)),
        "w_k": dense_init(ks[15], d, f, dtype),
        "w_v": dense_init(ks[16], f, d, dtype),
        "w_r": dense_init(jax.random.fold_in(ks[16], 1), d, d, dtype),
    }
    return {"ln1": layernorm_init(d, ks[18]), "att": att,
            "ln2": layernorm_init(d, ks[19]), "ffn": ffn}


def _shift(x: jax.Array, last: Optional[jax.Array]):
    """Token shift: (B, S, d) -> the previous token's activation (``last``,
    or zeros, before the first)."""
    first = (jnp.zeros_like(x[:, :1]) if last is None
             else last[:, None, :].astype(x.dtype))
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def _last(x: jax.Array, valid: Optional[jax.Array]):
    """(B, S, d) -> (B, d): each row's last valid step."""
    if valid is None:
        return x[:, -1]
    idx = jnp.maximum(jnp.sum(valid.astype(jnp.int32), axis=1) - 1, 0)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]


def _wkv_chunked(r, k, v, logw, bonus, chunk: int = WKV_CHUNK, s0=None):
    """r, k, v: (B,S,H,hd) f32; logw: (B,S,H,hd) <= 0; ``s0`` the carried
    (B,H,hd,hd) state (zeros when None).

    Returns (y (B,S,H,hd) f32, final state (B,H,hd,hd))."""
    B, S, H, hd = r.shape
    L = min(chunk, S)
    S_pad = ((S + L - 1) // L) * L
    if S_pad != S:
        # inert padding: k=0 (no contribution), logw=0 (state preserved)
        pz = lambda a: jnp.pad(a, [(0, 0), (0, S_pad - S)] + [(0, 0)] * (a.ndim - 2))
        r, k, v, logw = pz(r), pz(k), pz(v), pz(logw)
    S_orig, S = S, S_pad
    nc = S // L
    rc = r.reshape(B, nc, L, H, hd).swapaxes(0, 1)
    kc = k.reshape(B, nc, L, H, hd).swapaxes(0, 1)
    vc = v.reshape(B, nc, L, H, hd).swapaxes(0, 1)
    wc = logw.reshape(B, nc, L, H, hd).swapaxes(0, 1)

    tri_lower = (jnp.arange(L)[:, None] > jnp.arange(L)[None, :])   # s < t strict

    def body(S_in, inp):
        r_l, k_l, v_l, w_l = inp                               # (B,L,H,hd)
        lc = jnp.cumsum(w_l, axis=1)                           # (B,L,H,hd) L_t
        # decay from s to t (strict): exp(L_{t-1} - L_s) = exp(L_t - w_t - L_s)
        diff = (lc - w_l)[:, :, None] - lc[:, None, :]         # (B,t,s,H,hd)
        decay = jnp.where(tri_lower[None, :, :, None, None],
                          jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        # intra-chunk strict-past contribution
        scores = jnp.einsum("bthi,btshi,bshi->bths", r_l, decay, k_l,
                            precision=HI)
        y = jnp.einsum("bths,bshj->bthj", scores, v_l, precision=HI)
        # current-token bonus
        y += jnp.einsum("bthi,hi,bthi,bthj->bthj", r_l, bonus, k_l, v_l,
                        precision=HI)
        # carried state: y_t += sum_i r[t,i] exp(L_{t-1})[i] S_in[i,j]
        rstate = r_l * jnp.exp(lc - w_l)
        y += jnp.einsum("bthi,bhij->bthj", rstate, S_in, precision=HI)
        # state update: S_out = diag(exp(L_L)) S_in + sum_s exp(L_L - L_s) k_s v_s
        rem = jnp.exp(lc[:, -1:] - lc)                         # (B,L,H,hd)
        S_out = jnp.exp(lc[:, -1])[..., None] * S_in + jnp.einsum(
            "bshi,bshj->bhij", rem * k_l, v_l, precision=HI)
        return S_out, y

    S0 = jnp.zeros((B, H, hd, hd), jnp.float32) if s0 is None else s0
    S_fin, yc = jax.lax.scan(body, S0, (rc, kc, vc, wc))
    return yc.swapaxes(0, 1).reshape(B, S, H, hd)[:, :S_orig], S_fin


def _wkv_step(r, k, v, logw, bonus, s0):
    """One token of the recurrence. r, k, v, logw: (B,1,H,hd) f32; s0:
    (B,H,hd,hd). Returns (y (B,1,H,hd), new state)."""
    r, k, v, logw = r[:, 0], k[:, 0], v[:, 0], logw[:, 0]
    kv = k[..., :, None] * v[..., None, :]
    y = jnp.einsum("bhi,bhij->bhj", r, s0 + bonus[None, :, :, None] * kv,
                   precision=HI)
    return y[:, None], jnp.exp(logw)[..., None] * s0 + kv


def _group_norm(p: Params, y: jax.Array, eps: float):
    """Per-head GroupNorm with affine, float32. y: (B,S,H,hd) -> (B,S,d)."""
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), axis=-1, keepdims=True)
    yn = ((y - mu) * jax.lax.rsqrt(var + eps)).reshape(*y.shape[:2], -1)
    return yn * p["scale"] + p["bias"]


def rwkv6_time_mix(p: Params, cfg: ModelConfig, x: jax.Array,
                   wkv0: Optional[jax.Array] = None,
                   last: Optional[jax.Array] = None,
                   valid: Optional[jax.Array] = None, probe: bool = False):
    """x: (B, S, d) ln1 output. Returns (out (B,S,d), final WKV state,
    last valid x (B, d)[, probe]); ``probe`` adds the recurrence's inputs
    and output as it computed them, float32 (B, S, d): ``r``, ``k``, ``v``,
    ``logw`` and ``y`` (before ``ln_x``)."""
    B, S, d = x.shape
    H, hd = _dims(cfg)
    dt = x.dtype
    xf = x.astype(jnp.float32)
    xx = _shift(x, last).astype(jnp.float32) - xf
    xxx = (xf + xx * p["maa_x"]).astype(dt)
    m = jnp.tanh(xxx @ p["maa_w1"]).reshape(B, S, 5, -1)
    m = jnp.einsum("bsfr,frd->fbsd", m, p["maa_w2"]).astype(jnp.float32)
    xw, xk, xv, xr, xg = [
        (xf + xx * (p["maa_wkvrg"][i] + m[i])).astype(dt) for i in range(5)]
    r = (xr @ p["w_r"]).reshape(B, S, H, hd).astype(jnp.float32)
    k = (xk @ p["w_k"]).reshape(B, S, H, hd).astype(jnp.float32)
    v = (xv @ p["w_v"]).reshape(B, S, H, hd).astype(jnp.float32)
    g = jax.nn.silu(xg @ p["w_g"])
    lora = jnp.tanh(xw @ p["decay_w1"]).astype(jnp.float32) @ p[
        "decay_w2"].astype(jnp.float32)
    logw = -jnp.exp(p["decay"] + lora).reshape(B, S, H, hd)
    if valid is not None:
        m4 = valid[:, :, None, None]
        k = jnp.where(m4, k, 0.0)
        logw = jnp.where(m4, logw, 0.0)
    if S == 1 and valid is None:
        s0 = (jnp.zeros((B, H, hd, hd), jnp.float32) if wkv0 is None
              else wkv0)
        y, s_fin = _wkv_step(r, k, v, logw, p["bonus"], s0)
    else:
        y, s_fin = _wkv_chunked(r, k, v, logw, p["bonus"], s0=wkv0)
    seen = {"r": r, "k": k, "v": v, "logw": logw, "y": y}
    y = _group_norm(p["ln_x"], y, cfg.norm_eps * LN_X_EPS_SCALE)
    out = (y.astype(dt) * g) @ p["w_o"]
    if probe:
        return out, s_fin, _last(x, valid), {
            n: a.reshape(B, S, d) for n, a in seen.items()}
    return out, s_fin, _last(x, valid)


def rwkv6_channel_mix(p: Params, cfg: ModelConfig, x: jax.Array,
                      last: Optional[jax.Array] = None,
                      valid: Optional[jax.Array] = None):
    """x: (B, S, d) ln2 output. Returns (out, last valid x (B, d))."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    xx = _shift(x, last).astype(jnp.float32) - xf
    xk = (xf + xx * p["maa_k"]).astype(dt)
    xr = (xf + xx * p["maa_r"]).astype(dt)
    kv = jnp.square(jax.nn.relu(xk @ p["w_k"])) @ p["w_v"]
    out = jax.nn.sigmoid((xr @ p["w_r"]).astype(jnp.float32)).astype(dt) * kv
    return out, _last(x, valid)


def rwkv6_block(p: Params, cfg: ModelConfig, x: jax.Array,
                state: Optional[RWKVState] = None,
                valid: Optional[jax.Array] = None, probe: bool = False):
    """One block over (B, S, d) from ``state`` (zeros when None). Returns
    (x out, the state after each row's last valid step[, probe]):
    ``probe`` adds the time mix's (``rwkv6_time_mix``) with the block's
    input ``x_in`` and output ``x_out``."""
    eps = cfg.norm_eps
    x_in = x
    h = layernorm(p["ln1"], x, eps)
    att, wkv, last_t, *seen = rwkv6_time_mix(
        p["att"], cfg, h, None if state is None else state.wkv,
        None if state is None else state.shift_t, valid, probe)
    x = x + att
    h = layernorm(p["ln2"], x, eps)
    ffn, last_c = rwkv6_channel_mix(
        p["ffn"], cfg, h, None if state is None else state.shift_c, valid)
    x = x + ffn
    new_state = RWKVState(wkv=wkv, shift_t=last_t, shift_c=last_c)
    if probe:
        return x, new_state, dict(seen[0], x_in=x_in, x_out=x)
    return x, new_state


def init_rwkv_state(cfg: ModelConfig, batch: int) -> RWKVState:
    H, hd = _dims(cfg)
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    return RWKVState(
        wkv=jnp.zeros((batch, H, hd, hd), jnp.float32),
        shift_t=jnp.zeros((batch, d), dt),
        shift_c=jnp.zeros((batch, d), dt),
    )
