"""Jitted step builders: the one place train/prefill/serve computations are
assembled and compiled.

Every builder returns the jitted step plus the PartitionSpec trees its
operands live under (``sharding`` module semantics). The serve builder is
memoized per (cfg, mesh, max_len, retrieval-variant): the hardened server
keeps several degradation rungs alive at once (full exact plan, masked
probe at reduced nprobe, retrieval-off) and failover must not recompile a
rung it already has.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import BlockKind, ModelConfig, TrainConfig
from repro.core import retrieval as retrieval_mod
from repro.dist import sharding
from repro.models import lm
from repro.optim import optimizer


def dp_axes(mesh) -> Tuple[str, ...]:
    """Every mesh axis except the tensor/expert axis is data parallel."""
    return tuple(a for a in mesh.axis_names if a != "model")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, mesh, tc: TrainConfig, *,
                    causal_skip: bool = False, attn_p_bf16: bool = False,
                    pure_dp: bool = False, moe_a2a_int8: bool = False,
                    donate: bool = True):
    """Returns (step_fn, param_specs, opt_specs).

    ``step_fn(params, opt_state, batch, step) -> (params, opt_state,
    metrics)`` with metrics at least {loss, ce, aux, grad_norm, lr}.
    ``pure_dp`` drops the mesh from the model context (reference MoE path,
    no expert parallelism). ``tc.microbatches > 1`` accumulates gradients
    over a scan (activation memory / M).
    """
    ctx = lm.RunCtx(mesh=None if pure_dp else mesh, dp_axes=dp_axes(mesh),
                    causal_skip=causal_skip, attn_p_bf16=attn_p_bf16,
                    moe_a2a_int8=moe_a2a_int8, remat=tc.remat)
    micro = max(int(tc.microbatches), 1)

    def loss(params, batch):
        return lm.loss_fn(params, cfg, batch, ctx)

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def step(params, opt_state, batch, step_idx):
        if micro > 1:
            def split(x):
                return x.reshape((micro, x.shape[0] // micro) + x.shape[1:])
            mb = jax.tree_util.tree_map(split, batch)

            def body(carry, b):
                (lval, aux), grads = grad_fn(params, b)
                acc = jax.tree_util.tree_map(jnp.add, carry[0], grads)
                return (acc, carry[1] + lval,
                        jax.tree_util.tree_map(jnp.add, carry[2], aux)), None

            zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
            zero_a = {"ce": jnp.float32(0.0), "aux": jnp.float32(0.0)}
            (grads, lsum, asum), _ = jax.lax.scan(
                body, (zero_g, jnp.float32(0.0), zero_a), mb)
            grads = jax.tree_util.tree_map(lambda g: g / micro, grads)
            lval = lsum / micro
            aux = jax.tree_util.tree_map(lambda a: a / micro, asum)
        else:
            (lval, aux), grads = grad_fn(params, batch)
        new_params, new_opt, om = optimizer.update(
            grads, opt_state, params, tc, step_idx)
        metrics = dict(aux)
        metrics.update(om)
        metrics["loss"] = lval
        return new_params, new_opt, metrics

    pspecs = sharding.param_specs(cfg, mesh)
    oshapes = jax.eval_shape(
        lambda: optimizer.init(
            jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg)),
            tc))
    ospecs = sharding.replicated_like(oshapes)
    step_fn = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    return step_fn, pspecs, ospecs


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, mesh, seq_len: int, *,
                      causal_skip: bool = False, attn_p_bf16: bool = False,
                      attn_chunk: int = 1024, attn_impl: str = "xla"):
    """Returns (prefill_fn, param_specs); ``prefill_fn(params, batch) ->
    (logits, decode_state)`` over the full prompt."""
    ctx = lm.RunCtx(mesh=mesh, dp_axes=dp_axes(mesh),
                    causal_skip=causal_skip, attn_p_bf16=attn_p_bf16,
                    attn_chunk=attn_chunk, attn_impl=attn_impl)

    def prefill_fn(params, batch):
        return lm.prefill(params, cfg, batch["tokens"],
                          batch.get("prefix_emb"), ctx)

    return jax.jit(prefill_fn), sharding.param_specs(cfg, mesh)


# ---------------------------------------------------------------------------
# per-unit search steps (host-orchestrated fault-tolerant search)
# ---------------------------------------------------------------------------

# (bins, k) -> (hist_fn, topk_fn). dist/search.py calls one jitted hist and
# one jitted top-k per SURVIVING unit per query; units die and fail over
# mid-stream, so the callables must be shared across units and never
# rebuilt on the failover path (jit itself re-specializes per range shape,
# and equal-shape ranges share one executable).
_UNIT_STEP_CACHE: dict = {}


def unit_search_steps(bins: int, k: int):
    """Memoized jitted per-unit callables for dist/search.py:
    ``hist(q, x) -> (Q, bins)`` partial histogram and ``topk(q, x) ->
    (dists, ids)`` local top-k over ONE unit's row range."""
    key = (int(bins), int(k))
    hit = _UNIT_STEP_CACHE.get(key)
    if hit is not None:
        return hit
    from repro.kernels import ops

    hist = jax.jit(lambda q, x: ops.hamming_hist(q, x, key[0]))
    topk = jax.jit(lambda q, x: ops.hamming_topk(q, x, key[1], key[0]))
    out = (hist, topk)
    _UNIT_STEP_CACHE[key] = out
    return out


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

# (kind, cfg, mesh, max_len, with_retrieval, nprobe, id(probe_positions),
# select, recall_target) -> the built step. Degradation rung switches
# and failover paths re-request steps mid-serve; the cache makes that
# free.
_SERVE_CACHE: dict = {}


def _memo(kind, cfg, mesh, max_len, with_retrieval, nprobe, probe_positions,
          select, recall_target, build):
    try:
        key = (kind, cfg, mesh, int(max_len), bool(with_retrieval),
               int(nprobe),
               id(probe_positions) if probe_positions is not None else None,
               select,
               float(recall_target) if recall_target is not None else None)
        if key in _SERVE_CACHE:
            return _SERVE_CACHE[key]
    except TypeError:            # unhashable cfg/mesh: skip memoization
        key = None
    out = build()
    if key is not None:
        _SERVE_CACHE[key] = out
    return out


def _retrieve_and_mix(cfg, store, hidden, lm_logits, select, recall_target,
                      nprobe, probe_positions):
    """The kNN-LM head of a served step: search the keys ``hidden`` (Q, d),
    build the neighbour distribution and mix it with ``lm_logits`` (Q, V).
    Returns (mixed log-probs (Q, V) f32, the step's outputs)."""
    rcfg = cfg.retrieval
    codes, dists, ids = retrieval_mod.knn_search(
        store, hidden, rcfg, select=select, recall_target=recall_target,
        nprobe=nprobe, probe_positions=probe_positions)
    knn = retrieval_mod.knn_distribution(store, dists, ids, rcfg,
                                         cfg.vocab_size)
    mixed = retrieval_mod.interpolate(lm_logits, knn, rcfg.interpolation)
    return mixed, {"key": hidden, "codes": codes, "dists": dists, "ids": ids,
                   "lm_logits": lm_logits, "next": _greedy(mixed)}


def _greedy(scores):
    """The greedy token of each row, on the device: the host reads (B,)
    token ids, not the (B, V) distribution."""
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)


def make_serve_step(cfg: ModelConfig, mesh, max_len: int, *,
                    with_retrieval: Optional[bool] = None,
                    global_batch: Optional[int] = None,
                    nprobe: int = 0, probe_positions=None,
                    select: Optional[str] = None,
                    recall_target: Optional[float] = None):
    """Returns (serve_fn, param_specs, state_specs).

    ``serve_fn(params, token (B,1), state, active (B,)[, store]) ->
    (log-probs (B,1,V) f32, new_state, outs)`` — one decode step for every
    active slot; the store argument exists iff retrieval is on. ``outs``
    holds what the step computed on the way: ``key`` (B, d), the hidden
    state after the final norm (the retrieval key), ``lm_logits`` (B, V),
    ``next`` (B,) int32, the argmax of the served distribution (greedy
    decoding), and with retrieval ``codes`` (B, W) uint32 (the keys' ITQ
    codes), ``dists`` and ``ids`` (B, k); for RWKV-6 also ``probe``, the
    last block's recurrence as the step computed it (``lm.decode_step``).
    Without retrieval the served scores are the LM logits. ``nprobe > 0`` (with the store's
    hamming-prefix ``probe_positions``) builds the DEGRADED serving
    variant: masked IVF-style probe over the layout at reduced nprobe
    instead of the full exact plan; ``select="approx"`` + ``recall_target``
    builds the APPROX rung — the compute-bound MXU partial-reduce tier at a
    bounded recall loss. ``global_batch`` is accepted for dry-run symmetry;
    shapes come from the operands.
    """
    if with_retrieval is None:
        with_retrieval = cfg.retrieval.enabled
    ctx = lm.RunCtx(mesh=mesh, dp_axes=dp_axes(mesh))

    probe = cfg.block_pattern[0] == BlockKind.RWKV6

    def build():
        def decode(params, token, state, active):
            with jax.named_scope("knnlm.lm"):
                logits, new_state, hidden, *seen = lm.decode_step(
                    params, cfg, token, state, ctx, active=active,
                    return_hidden=True, return_probe=probe)
            return (logits[:, 0, :], new_state, hidden[:, 0, :],
                    {"probe": seen[0]} if probe else {})

        if with_retrieval:
            def serve_fn_py(params, token, state, active, store):
                logits, new_state, hidden, extra = decode(params, token,
                                                          state, active)
                mixed, outs = _retrieve_and_mix(
                    cfg, store, hidden, logits, select, recall_target,
                    nprobe, probe_positions)
                return mixed[:, None, :], new_state, {**outs, **extra}
        else:
            def serve_fn_py(params, token, state, active):
                logits, new_state, hidden, extra = decode(params, token,
                                                          state, active)
                return (logits.astype(jnp.float32)[:, None, :], new_state,
                        {"key": hidden, "lm_logits": logits,
                         "next": _greedy(logits), **extra})

        return (jax.jit(serve_fn_py), sharding.param_specs(cfg, mesh),
                sharding.decode_state_specs(cfg, mesh))

    return _memo("decode", cfg, mesh, max_len, with_retrieval, nprobe,
                 probe_positions, select, recall_target, build)


def make_admit_step(cfg: ModelConfig, mesh, max_len: int, *,
                    with_retrieval: Optional[bool] = None,
                    nprobe: int = 0, probe_positions=None,
                    select: Optional[str] = None,
                    recall_target: Optional[float] = None):
    """The slot prefill of serving admission, at the same retrieval variant
    as ``make_serve_step``'s arguments name.

    ``admit_fn(params, tokens (A,S), lengths (A,), slots (A,), state[,
    store]) -> (log-probs (A,V) f32, new_state, outs)``: one prefill of the
    right-padded prompts (``lm.prefill_last``, scope ``knnlm.prefill``)
    from the recurrent state the slots hold (the server zeroes them first),
    its final state written into those slots of ``state`` only (slots out
    of range are padding rows: read as zeros, never written), and the first
    token's distribution from each prompt's last hidden state with
    retrieval and interpolation. ``outs`` as ``make_serve_step``'s.
    """
    if with_retrieval is None:
        with_retrieval = cfg.retrieval.enabled
    ctx = lm.RunCtx(mesh=mesh, dp_axes=dp_axes(mesh))

    def build():
        def prefill(params, tokens, lengths, slots, state):
            with jax.named_scope("knnlm.prefill"):
                hidden, logits, rows = lm.prefill_last(
                    params, cfg, tokens, lengths,
                    lm.read_rows(cfg, state, slots), ctx)
                return hidden, logits, lm.write_rows(cfg, state, rows, slots)

        if with_retrieval:
            def admit_fn(params, tokens, lengths, slots, state, store):
                hidden, logits, new_state = prefill(params, tokens, lengths,
                                                    slots, state)
                mixed, outs = _retrieve_and_mix(
                    cfg, store, hidden, logits, select, recall_target,
                    nprobe, probe_positions)
                return mixed, new_state, outs
        else:
            def admit_fn(params, tokens, lengths, slots, state):
                hidden, logits, new_state = prefill(params, tokens, lengths,
                                                    slots, state)
                return (logits.astype(jnp.float32), new_state,
                        {"key": hidden, "lm_logits": logits,
                         "next": _greedy(logits)})

        return jax.jit(admit_fn)

    return _memo("admit", cfg, mesh, max_len, with_retrieval, nprobe,
                 probe_positions, select, recall_target, build)
