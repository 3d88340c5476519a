"""Unified causal LM over every assigned architecture family.

The layer stack is a ``lax.scan`` over stacked per-layer params (one compiled
block body regardless of depth — essential for 95-layer dry-run compiles).
Hybrid archs (zamba2) nest the scan: groups of Mamba2 blocks with one
weight-shared attention block applied per group.

Entry points:
  forward        — full-sequence logits (training / scoring)
  prefill        — full sequence + returns the decode state (KV caches / SSM
                   states / RWKV states)
  prefill_last   — serving admission: right-padded prompts of per-row
                   lengths from a per-row recurrent state; returns the last
                   prompt token's hidden state and logits and the rows'
                   decode state (``write_rows`` puts it into slots)
  decode_step    — one token against the decode state
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import BlockKind, ModelConfig
from repro.models import frontends
from repro.models.attention import (KVCache, attention_decode, attention_init,
                                    attention_prefill)
from repro.models.layers import (Params, cross_entropy, dense_init, embed,
                                 embedding_init, layernorm, layernorm_init,
                                 mlp, mlp_init, rmsnorm, rmsnorm_init,
                                 unembed)
from repro.models.mamba2 import (MambaState, init_mamba_state, mamba2_forward,
                                 mamba2_init, mamba2_step)
from repro.models.moe import moe_forward, moe_init
from repro.models.rwkv6 import init_rwkv_state, rwkv6_block, rwkv6_block_init


@dataclasses.dataclass
class RunCtx:
    """Execution-context knobs threaded through the model (static python)."""

    mesh: Optional[Mesh] = None
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    ep_axis: str = "model"
    causal_skip: bool = False          # triangular attention schedule (§Perf)
    attn_p_bf16: bool = False          # bf16 probability tensor (§Perf)
    moe_a2a_int8: bool = False         # quantized MoE dispatch (§Perf)
    attn_impl: str = "xla"             # 'xla' | 'flash' (Pallas fwd kernel)
    remat: bool = True
    attn_chunk: int = 1024
    moe_strategy: str = "auto"
    # logical activation sharder: (x, logical_dims) -> x; identity by default
    shard: Callable[[jax.Array, Tuple[Optional[str], ...]], jax.Array] = (
        lambda x, dims: x)


DEFAULT_CTX = RunCtx()


def _stacked_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# per-family block init/apply
# ---------------------------------------------------------------------------

def _attn_mlp_block_init(key, cfg: ModelConfig, dtype, d_ff: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(cfg.d_model),
        "attn": attention_init(k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                               cfg.resolved_head_dim, dtype),
        "ln2": rmsnorm_init(cfg.d_model),
        "mlp": mlp_init(k2, cfg.d_model, d_ff, cfg.mlp_activation, dtype),
    }


def _moe_block_init(key, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(cfg.d_model),
        "attn": attention_init(k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                               cfg.resolved_head_dim, dtype),
        "ln2": rmsnorm_init(cfg.d_model),
        "moe": moe_init(k2, cfg, dtype),
    }


def _mamba_block_init(key, cfg: ModelConfig, dtype) -> Params:
    return {"ln": rmsnorm_init(cfg.d_model), "mamba": mamba2_init(key, cfg, dtype)}


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_blocks, k_head, k_shared, k_fe = jax.random.split(key, 5)
    params: Params = {"embed": embedding_init(k_emb, cfg.vocab_size, cfg.d_model, dtype)}

    kind = cfg.block_pattern[0]
    if cfg.shared_attn_every:      # zamba2-style hybrid
        groups = cfg.num_layers // cfg.shared_attn_every
        per_group = cfg.shared_attn_every
        params["blocks"] = _stacked_init(
            lambda k: _stacked_init(lambda kk: _mamba_block_init(kk, cfg, dtype), k, per_group),
            k_blocks, groups)
        params["shared_attn"] = _attn_mlp_block_init(k_shared, cfg, dtype, cfg.d_ff)
    elif kind == BlockKind.ATTENTION:
        params["blocks"] = _stacked_init(
            lambda k: _attn_mlp_block_init(k, cfg, dtype, cfg.d_ff), k_blocks, cfg.num_layers)
    elif kind == BlockKind.MOE:
        params["blocks"] = _stacked_init(
            lambda k: _moe_block_init(k, cfg, dtype), k_blocks, cfg.num_layers)
    elif kind == BlockKind.MAMBA2:
        params["blocks"] = _stacked_init(
            lambda k: _mamba_block_init(k, cfg, dtype), k_blocks, cfg.num_layers)
    elif kind == BlockKind.RWKV6:
        params["blocks"] = _stacked_init(
            lambda k: rwkv6_block_init(k, cfg, dtype), k_blocks, cfg.num_layers)
    else:
        raise ValueError(kind)

    if kind == BlockKind.RWKV6:
        # Finch: LayerNorms with bias, ln0 on the embedding, ln_out last
        k_ln0, k_out = jax.random.split(jax.random.fold_in(k_head, 1))
        params["ln0"] = layernorm_init(cfg.d_model, k_ln0)
        params["final_norm"] = layernorm_init(cfg.d_model, k_out)
    else:
        params["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["unembed"] = embedding_init(k_head, cfg.vocab_size, cfg.d_model, dtype)
    if cfg.frontend != "none":
        params["frontend"] = {
            "proj": dense_init(k_fe, frontends.frontend_dim(cfg), cfg.d_model, dtype)}
    return params


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    import math
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    total = sum(math.prod(l.shape) if l.shape else 1
                for l in jax.tree_util.tree_leaves(shapes))
    if active_only and cfg.moe is not None:
        expert_leaves = jax.tree_util.tree_leaves(
            {k: shapes["blocks"]["moe"][k] for k in ("w_gate", "w_up", "w_out")})
        expert_total = sum(math.prod(l.shape) for l in expert_leaves)
        active_frac = cfg.moe.experts_per_token / cfg.moe.num_experts
        total = total - expert_total + int(expert_total * active_frac)
    return total


# ---------------------------------------------------------------------------
# block apply (full sequence)
# ---------------------------------------------------------------------------

def _apply_attn_mlp(p, cfg, ctx: RunCtx, x, positions, want_cache: bool):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if want_cache:
        a, cache = attention_prefill(p["attn"], h, positions, cfg.rope_theta,
                                     chunk=ctx.attn_chunk,
                                     causal_skip=ctx.causal_skip,
                                     p_bf16=ctx.attn_p_bf16,
                                     impl=ctx.attn_impl, return_cache=True)
    else:
        a = attention_prefill(p["attn"], h, positions, cfg.rope_theta,
                              chunk=ctx.attn_chunk, causal_skip=ctx.causal_skip,
                              p_bf16=ctx.attn_p_bf16, impl=ctx.attn_impl)
        cache = None
    x = x + a
    x = ctx.shard(x, ("batch", "seq", None))
    x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg.mlp_activation)
    x = ctx.shard(x, ("batch", "seq", None))
    return x, cache


def _apply_moe_block(p, cfg, ctx: RunCtx, x, positions, want_cache: bool):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if want_cache:
        a, cache = attention_prefill(p["attn"], h, positions, cfg.rope_theta,
                                     chunk=ctx.attn_chunk,
                                     causal_skip=ctx.causal_skip,
                                     p_bf16=ctx.attn_p_bf16,
                                     impl=ctx.attn_impl, return_cache=True)
    else:
        a = attention_prefill(p["attn"], h, positions, cfg.rope_theta,
                              chunk=ctx.attn_chunk, causal_skip=ctx.causal_skip,
                              p_bf16=ctx.attn_p_bf16, impl=ctx.attn_impl)
        cache = None
    x = x + a
    y, aux = moe_forward(p["moe"], cfg, rmsnorm(p["ln2"], x, cfg.norm_eps),
                         mesh=ctx.mesh, dp_axes=ctx.dp_axes, ep_axis=ctx.ep_axis,
                         strategy=ctx.moe_strategy, a2a_int8=ctx.moe_a2a_int8)
    x = ctx.shard(x + y, ("batch", "seq", None))
    return x, aux, cache


def _apply_mamba_block(p, cfg, ctx: RunCtx, x, want_state: bool = False,
                       lengths=None, state0=None):
    h = rmsnorm(p["ln"], x, cfg.norm_eps)
    if want_state:
        y, st = mamba2_forward(p["mamba"], cfg, h, return_state=True,
                               lengths=lengths, state0=state0)
        return ctx.shard(x + y, ("batch", "seq", None)), st
    y = mamba2_forward(p["mamba"], cfg, h)
    return ctx.shard(x + y, ("batch", "seq", None))


def _valid(lengths, S: int):
    """(B,) lengths -> (B, S) mask of each row's own steps (None: all)."""
    if lengths is None:
        return None
    return jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]


# ---------------------------------------------------------------------------
# forward / prefill
# ---------------------------------------------------------------------------

def _embed_tokens(params, cfg, tokens):
    x = embed(params["embed"], tokens)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if "ln0" in params:                 # Finch normalizes the embedding
        x = layernorm(params["ln0"], x, cfg.norm_eps)
    return x


def _final_norm(params, cfg, x):
    if "bias" in params["final_norm"]:  # Finch's ln_out
        return layernorm(params["final_norm"], x, cfg.norm_eps)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps)


def _embed_inputs(params, cfg, tokens, prefix_emb):
    x = _embed_tokens(params, cfg, tokens)
    if cfg.frontend != "none":
        assert prefix_emb is not None, f"{cfg.name} requires frontend embeddings"
        pre = prefix_emb.astype(x.dtype) @ params["frontend"]["proj"]
        x = jnp.concatenate([pre, x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return x, positions


def _run_stack(params, cfg: ModelConfig, ctx: RunCtx, x, positions,
               want_cache: bool = False, lengths=None, state0=None):
    """Returns (hidden, aux_loss, caches-or-None). ``lengths`` (B,) marks
    right padding and ``state0`` (the recurrent part of a decode state's
    cache, layer-stacked) is the state the rows start from; both only with
    ``want_cache``, and attention caches always start empty."""
    kind = cfg.block_pattern[0]
    aux0 = jnp.zeros((), jnp.float32)

    if cfg.shared_attn_every:
        def group_body(carry, xs):
            x, aux = carry
            p_group, m0 = xs if state0 is not None else (xs, None)

            def inner(x, xs_l):
                p_layer, st_l = xs_l if m0 is not None else (xs_l, None)
                if want_cache:
                    return _apply_mamba_block(p_layer, cfg, ctx, x, True,
                                              lengths, st_l)
                return _apply_mamba_block(p_layer, cfg, ctx, x), None

            x, msts = jax.lax.scan(
                inner, x, p_group if m0 is None else (p_group, m0))
            x2, cache = _apply_attn_mlp(params["shared_attn"], cfg, ctx, x,
                                        positions, want_cache)
            return (x2, aux), (msts, cache)

        group_fn = jax.checkpoint(group_body) if ctx.remat else group_body
        xs = params["blocks"] if state0 is None else (
            params["blocks"], state0["mamba"])
        (x, aux), caches = jax.lax.scan(group_fn, (x, aux0), xs)
        return x, aux, caches

    if kind == BlockKind.ATTENTION:
        def body(carry, p_layer):
            x, aux = carry
            x, cache = _apply_attn_mlp(p_layer, cfg, ctx, x, positions, want_cache)
            return (x, aux), cache
    elif kind == BlockKind.MOE:
        def body(carry, p_layer):
            x, aux = carry
            x, aux_l, cache = _apply_moe_block(p_layer, cfg, ctx, x, positions,
                                               want_cache)
            return (x, aux + aux_l), cache
    elif kind == BlockKind.MAMBA2:
        def body(carry, xs):
            x, aux = carry
            p_layer, st_l = xs if state0 is not None else (xs, None)
            if want_cache:
                x, st = _apply_mamba_block(p_layer, cfg, ctx, x, True,
                                           lengths, st_l)
                return (x, aux), st
            return (_apply_mamba_block(p_layer, cfg, ctx, x), aux), None
    elif kind == BlockKind.RWKV6:
        valid = _valid(lengths, x.shape[1])

        def body(carry, xs):
            x, aux = carry
            p_layer, st_l = xs if state0 is not None else (xs, None)
            x, st = rwkv6_block(p_layer, cfg, x, st_l, valid)
            x = ctx.shard(x, ("batch", "seq", None))
            return (x, aux), (st if want_cache else None)
    else:
        raise ValueError(kind)

    body_fn = jax.checkpoint(body) if ctx.remat else body
    recurrent = kind in (BlockKind.MAMBA2, BlockKind.RWKV6)
    xs = (params["blocks"], state0) if (recurrent and state0 is not None) \
        else params["blocks"]
    (x, aux), caches = jax.lax.scan(body_fn, (x, aux0), xs)
    return x, aux, caches


def forward(params, cfg: ModelConfig, tokens, prefix_emb=None,
            ctx: RunCtx = DEFAULT_CTX, return_hidden: bool = False):
    """tokens: (B, S) -> logits (B, S(+P), V)."""
    x, positions = _embed_inputs(params, cfg, tokens, prefix_emb)
    x = ctx.shard(x, ("batch", "seq", None))
    x, aux, _ = _run_stack(params, cfg, ctx, x, positions, want_cache=False)
    h = _final_norm(params, cfg, x)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(table, h)
    logits = ctx.shard(logits, ("batch", "seq", "vocab"))
    if return_hidden:
        return logits, aux, h
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch, ctx: RunCtx = DEFAULT_CTX):
    """batch: {'tokens': (B,S), 'labels': (B,S), optional 'prefix_emb'}."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("prefix_emb"), ctx)
    P = logits.shape[1] - batch["labels"].shape[1]
    if P:                                  # drop frontend positions from loss
        logits = logits[:, P:]
    ce = cross_entropy(logits, batch["labels"], batch.get("mask"))
    aux_w = cfg.moe.router_aux_loss if cfg.moe is not None else 0.0
    return ce + aux_w * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, tokens, prefix_emb=None,
            ctx: RunCtx = DEFAULT_CTX):
    """Full-sequence forward that also returns the decode state."""
    x, positions = _embed_inputs(params, cfg, tokens, prefix_emb)
    x = ctx.shard(x, ("batch", "seq", None))
    x, aux, caches = _run_stack(params, cfg, ctx, x, positions, want_cache=True)
    if cfg.shared_attn_every:
        caches = {"kv": caches[1], "mamba": caches[0]}
    h = _final_norm(params, cfg, x)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(table, h)
    state = {"pos": jnp.full((tokens.shape[0],), x.shape[1], jnp.int32),
             "cache": caches}
    return logits, state


def prefill_last(params, cfg: ModelConfig, tokens, lengths, state=None,
                 ctx: RunCtx = DEFAULT_CTX):
    """Serving admission. tokens: (A, S) right-padded prompts, row a holding
    ``lengths[a]`` >= 1 tokens; ``state``: a decode state of A rows
    (``read_rows``) whose recurrent part the rows start from (None: zeros;
    attention caches always start empty, at position 0).

    Returns (hidden (A, d) at each row's last prompt token after the final
    norm, its logits (A, V), and the rows' decode state with ``pos`` =
    ``lengths``; the attention cache is S long, for ``write_rows``)."""
    if cfg.frontend != "none":
        raise ValueError(f"{cfg.name}: slot prefill takes no frontend inputs")
    x = _embed_tokens(params, cfg, tokens)
    A, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (A, S))
    state0 = None
    if state is not None and cfg.block_pattern[0] in (BlockKind.MAMBA2,
                                                      BlockKind.RWKV6):
        state0 = state["cache"]
    elif state is not None and cfg.shared_attn_every:
        state0 = {"mamba": state["cache"]["mamba"]}
    x, _, caches = _run_stack(params, cfg, ctx, x, positions, want_cache=True,
                              lengths=lengths, state0=state0)
    if cfg.shared_attn_every:
        caches = {"kv": caches[1], "mamba": caches[0]}
    idx = jnp.maximum(lengths - 1, 0)
    h = _final_norm(params, cfg, jnp.take_along_axis(
        x, idx[:, None, None], axis=1)[:, 0])
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return h, unembed(table, h), {"pos": lengths.astype(jnp.int32),
                                  "cache": caches}


def _row_axes(cfg: ModelConfig, cache):
    """The batch axis of every leaf of a decode state's cache: 1 under the
    layer axis, 2 for the hybrid's (groups, per-group) Mamba states."""
    if cfg.shared_attn_every:
        return {"kv": jax.tree_util.tree_map(lambda _: 1, cache["kv"]),
                "mamba": jax.tree_util.tree_map(lambda _: 2, cache["mamba"])}
    return jax.tree_util.tree_map(lambda _: 1, cache)


def read_rows(cfg: ModelConfig, state, slots):
    """Rows ``slots`` (A,) of a batch decode state; slots out of range read
    as zeros."""
    cache = jax.tree_util.tree_map(
        lambda a, ax: jnp.take(a, slots, axis=ax, mode="fill", fill_value=0),
        state["cache"], _row_axes(cfg, state["cache"]))
    pos = jnp.take(state["pos"], slots, mode="fill", fill_value=0)
    return {"pos": pos, "cache": cache}


def write_rows(cfg: ModelConfig, state, rows, slots):
    """Write ``rows`` (a decode state of A rows, as ``prefill_last`` returns
    it) into rows ``slots`` (A,) of the batch decode ``state``. An attention
    cache fills its first S positions; slots out of range are dropped."""
    def put(dst, src, ax):
        idx = (slice(None),) * ax + (slots,) + tuple(
            slice(0, n) for n in src.shape[ax + 1:])
        return dst.at[idx].set(src.astype(dst.dtype), mode="drop")

    cache = jax.tree_util.tree_map(put, state["cache"], rows["cache"],
                                   _row_axes(cfg, state["cache"]))
    pos = jnp.asarray(state["pos"], jnp.int32).at[slots].set(
        rows["pos"], mode="drop")
    return {"pos": pos, "cache": cache}


def zero_rows(cfg: ModelConfig, state, slots):
    """The batch decode ``state`` with rows ``slots`` reset: every recurrent
    state and cache entry zero, ``pos`` 0."""
    def zero(dst, ax):
        return dst.at[(slice(None),) * ax + (slots,)].set(0, mode="drop")

    cache = jax.tree_util.tree_map(zero, state["cache"],
                                   _row_axes(cfg, state["cache"]))
    pos = jnp.asarray(state["pos"], jnp.int32).at[slots].set(0, mode="drop")
    return {"pos": pos, "cache": cache}


def _keep_active(active, new, old):
    """Select updated state rows only where active (batch is axis 0)."""
    if active is None:
        return new
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(
            active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, old)


def _decode_attn_mlp(p, cfg, ctx, x, cache: KVCache, pos, active):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    y, new_cache = attention_decode(p["attn"], h, cache, pos, cfg.rope_theta,
                                    active=active)
    x = x + y
    x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg.mlp_activation)
    return x, new_cache


def _decode_moe_block(p, cfg, ctx, x, cache: KVCache, pos, active):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    y, new_cache = attention_decode(p["attn"], h, cache, pos, cfg.rope_theta,
                                    active=active)
    x = x + y
    y2, _ = moe_forward(p["moe"], cfg, rmsnorm(p["ln2"], x, cfg.norm_eps),
                        mesh=ctx.mesh, dp_axes=ctx.dp_axes, ep_axis=ctx.ep_axis,
                        strategy="allgather" if ctx.mesh is not None else "auto")
    return x + y2, new_cache


def decode_step(params, cfg: ModelConfig, token, state, ctx: RunCtx = DEFAULT_CTX,
                active=None, return_hidden: bool = False,
                return_probe: bool = False):
    """token: (B, 1) int32; state from ``init_decode_state`` or ``prefill``.
    ``pos`` may be per-row; rows with ``active`` False (continuous batching
    free slots) keep their state unchanged.

    Returns (logits (B,1,V), new_state[, hidden][, probe]). ``probe``
    (RWKV-6 only) is the last block as this step computed it, per row:
    its input state (``wkv``, ``shift_t``, ``shift_c``) and
    ``rwkv6_block``'s probe."""
    if return_probe and cfg.block_pattern[0] != BlockKind.RWKV6:
        raise ValueError(f"{cfg.name}: only an RWKV-6 stack has a probe")
    pos = jnp.broadcast_to(jnp.asarray(state["pos"], jnp.int32),
                           (token.shape[0],))
    x = _embed_tokens(params, cfg, token)
    kind = cfg.block_pattern[0]

    if cfg.shared_attn_every:
        def group_body(x, xs):
            p_group, kv_g, m_g = xs

            def inner(x, xs_l):
                p_layer, st_l = xs_l
                h = rmsnorm(p_layer["ln"], x, cfg.norm_eps)
                y, new_st = mamba2_step(p_layer["mamba"], cfg, h, st_l)
                return x + y, _keep_active(active, new_st, st_l)

            x, new_m = jax.lax.scan(inner, x, (p_group, m_g))
            x, new_kv = _decode_attn_mlp(params["shared_attn"], cfg, ctx, x,
                                         kv_g, pos, active)
            return x, (new_kv, new_m)

        x, (new_kv, new_m) = jax.lax.scan(
            group_body, x, (params["blocks"], state["cache"]["kv"],
                            state["cache"]["mamba"]))
        new_cache = {"kv": new_kv, "mamba": new_m}
    elif kind == BlockKind.ATTENTION:
        def body(x, xs):
            p_layer, cache_l = xs
            return _decode_attn_mlp(p_layer, cfg, ctx, x, cache_l, pos, active)

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], state["cache"]))
    elif kind == BlockKind.MOE:
        def body(x, xs):
            p_layer, cache_l = xs
            return _decode_moe_block(p_layer, cfg, ctx, x, cache_l, pos, active)

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], state["cache"]))
    elif kind == BlockKind.RWKV6:
        def body(x, xs):
            p_layer, st_l = xs
            x, new_st, *seen = rwkv6_block(p_layer, cfg, x, st_l,
                                           probe=return_probe)
            return x, (_keep_active(active, new_st, st_l), *seen)

        x, (new_cache, *seen) = jax.lax.scan(
            body, x, (params["blocks"], state["cache"]))
        if return_probe:
            st_in = state["cache"]
            probe = {n: a[-1][:, 0] for n, a in seen[0].items()}
            probe.update(wkv=st_in.wkv[-1], shift_t=st_in.shift_t[-1],
                         shift_c=st_in.shift_c[-1])
    else:
        raise ValueError(kind)

    h = _final_norm(params, cfg, x)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(table, h)
    new_pos = pos + (1 if active is None else active.astype(jnp.int32))
    new_state = {"pos": new_pos, "cache": new_cache}
    out = (logits, new_state)
    if return_hidden:
        out += (h,)
    if return_probe:
        out += (probe,)
    return out


def pad_decode_state(cfg: ModelConfig, state, max_len: int):
    """Grow the KV-cache capacity of a prefill state to ``max_len``."""
    def pad_kv(c: KVCache) -> KVCache:
        def pad(a):
            extra = max_len - a.shape[2]
            if extra <= 0:
                return a
            pad_widths = [(0, 0)] * a.ndim
            pad_widths[2] = (0, extra)
            return jnp.pad(a, pad_widths)
        return KVCache(k=pad(c.k), v=pad(c.v))

    cache = state["cache"]
    if cfg.shared_attn_every:
        cache = {"kv": pad_kv(cache["kv"]), "mamba": cache["mamba"]}
    elif isinstance(cache, KVCache):
        cache = pad_kv(cache)
    return {"pos": state["pos"], "cache": cache}


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    """Zero decode state with capacity ``max_len`` (the dry-run's KV cache)."""
    dt = jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim

    def kv(n_stack):
        shape = (n_stack, batch, max_len, cfg.num_kv_heads, hd)
        return KVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))

    pos0 = jnp.zeros((batch,), jnp.int32)
    if cfg.shared_attn_every:
        groups = cfg.num_layers // cfg.shared_attn_every
        per_group = cfg.shared_attn_every
        ms = init_mamba_state(cfg, batch)
        ms = jax.tree_util.tree_map(
            lambda a: jnp.zeros((groups, per_group) + a.shape, a.dtype), ms)
        return {"pos": pos0, "cache": {"kv": kv(groups), "mamba": ms}}
    kind = cfg.block_pattern[0]
    if kind in (BlockKind.ATTENTION, BlockKind.MOE):
        return {"pos": pos0, "cache": kv(cfg.num_layers)}
    if kind == BlockKind.RWKV6:
        st = init_rwkv_state(cfg, batch)
        st = jax.tree_util.tree_map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), st)
        return {"pos": pos0, "cache": st}
    if kind == BlockKind.MAMBA2:
        ms = init_mamba_state(cfg, batch)
        ms = jax.tree_util.tree_map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), ms)
        return {"pos": pos0, "cache": ms}
    raise ValueError(kind)
