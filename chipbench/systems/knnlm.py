"""kNN-LM decode on RWKV-6 Finch through the serving runtime.

Set-up, in the order that fits: the datastore (2^24 uniform 256-bit codes
and next-token values from the seed) and its ``hamming_prefix`` layout, then
the LM's weights, drawn from the seed at the published shapes by the
benchmark (``refs/rwkv6_knnlm.init_params``) and loaded into the program's
parameter tree (a shape or dtype that differs fails the run), then ITQ
tables fitted by the benchmark (``fit_itq``) on the served model's hidden
states over seeded tokens, then ``runtime/server.Server`` with one slot per
client. Warm-up runs one full cycle of the closed loop, whose admission
compiles the prefill bucket of the cohort's longest prompt (the top bucket:
16 prompts of 64-512 tokens all fit a smaller one with probability 1.4e-6),
and half of the next cycle: the cohort's requests end together, so the
window then holds reused slots and exactly one admission, mid-window, where
a window that began at a cycle's start would hold one or two.

The window drives ``Server.submit`` and ``Server.tick()``: each tick serves
one token to every live request, each token one retrieval query. Clients are
closed-loop: a finished request is followed at once by the client's next.

The check samples requests admitted in the window, lets them finish after
the window closes, frees the program, and compares what the timed path
produced (``Server.step_log``) with the plain float32 reference
(``refs/rwkv6_knnlm.py``) stage by stage:

- ``lm_logp_err``: the LM's log-softmax at every served token against the
  reference's forward from a zero state over prompt + served tokens
  (teacher-forced); ``key_rel_err``: the retrieval key's relative error.
  Both toleranced, at the configuration's precision (bf16 weights and
  activations, float32 WKV state).
- ``block_err``: the last block's output, recomputed in float32 from the
  input and state the decode step gave it (its ``probe``), against the
  step's own: one block's error at the stated precision, without the
  other 23 layers' on top; toleranced.
- ``wkv_err``: the last block's WKV recurrence recomputed in float32 from
  the step's own r, k, v, log-decay and state: its output y, and the state
  the request's next step read; float32-level, so a state carried at a
  lower precision fails it.
- ``code_bits_off``: query bits unlike the float32 ITQ sign of the system's
  own key, beyond the float32 accumulation margin (``itq_project``); 0.
- ``wrong_rows``: served ``(dists, ids)`` that are not an exact k-NN answer
  for the system's own codes (``refs/hamming_topk.py``); 0.
- ``mix_err``: served log-probs against the float32 mix of the system's LM
  logits with the neighbour distribution rebuilt from its own
  ``(dists, ids)``.
- ``tokens_off``: served tokens that are not the argmax of their own
  served distribution (greedy decoding); 0.
- ``reused_short``, ``first_token_missing``: sampled requests admitted into
  a reused slot short of one; sampled requests whose first token was not
  served from their admission; 0.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time
from typing import Any, Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from harness import lm_work, traffic as traffic_mod, work
from refs import hamming_topk, rwkv6_knnlm as ref

# the configuration's keys and the program's ModelConfig fields they name
MODEL_KEYS = {
    "num_hidden_layers": lambda m: m.num_layers,
    "hidden_size": lambda m: m.d_model,
    "head_size": lambda m: m.rwkv.head_dim,
    "intermediate_size": lambda m: m.d_ff,
    "vocab_size": lambda m: m.vocab_size,
    "time_mix_extra_dim": lambda m: m.rwkv.mix_lora,
    "time_decay_extra_dim": lambda m: m.rwkv.decay_lora,
    "layer_norm_epsilon": lambda m: m.norm_eps,
    "tie_word_embeddings": lambda m: m.tie_embeddings,
    "dtype": lambda m: m.dtype,
}


@dataclasses.dataclass
class State:
    cfg: Any
    params: Any
    codes: jax.Array
    values: jax.Array
    itq: Any
    server: Any
    rng: np.random.Generator
    clients: List[Any]
    requests: Dict[int, Any]
    slot_uses: np.ndarray
    reused: Dict[int, bool]
    uid: int = 0


def _nospan(name: str):
    return contextlib.nullcontext()


def _program():
    """The program's serving modules; a RuntimeError, at once, when they
    lack slot prefill (a program older than this cell)."""
    from repro.dist import steps
    from repro.models import rwkv6
    if not (hasattr(steps, "make_admit_step")
            and hasattr(rwkv6, "rwkv6_block")):
        raise RuntimeError("the program has no slot-prefill server "
                           "(steps.make_admit_step) or Finch block; it "
                           "cannot run this configuration")


def model_config(config: dict):
    """The program's ModelConfig of the configuration, retrieval set as the
    configuration states; a ValueError where a width differs."""
    from repro.configs import get_config
    cfg = get_config(config["arch"])
    off = {k: (config[k], f(cfg)) for k, f in MODEL_KEYS.items()
           if config[k] != f(cfg)}
    if off:
        raise ValueError(f"{config['arch']} differs from the configuration: "
                         f"{off}")
    return dataclasses.replace(cfg, retrieval=dataclasses.replace(
        cfg.retrieval, enabled=True, code_bits=config["code_bits"],
        datastore_size=config["rows"], k=config["k"],
        interpolation=config["interpolation"], layout=config["layout"]))


def load_params(config: dict, cfg, key):
    """The benchmark's Finch weights (``ref.init_params``) as the program's
    parameter tree; a ValueError where a leaf's name, shape or dtype is not
    the program's."""
    from repro.models import lm
    tree = jax.eval_shape(lambda k: lm.init_params(k, cfg), key)
    have = {".".join(p.key for p in path): (tuple(a.shape), jnp.dtype(a.dtype))
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}
    want = {n: (tuple(shape), jnp.dtype(dt))
            for n, (shape, dt) in ref.param_shapes(config).items()}
    if have != want:
        off = sorted(n for n in have.keys() | want.keys()
                     if have.get(n) != want.get(n))
        raise ValueError("the program's Finch parameters differ from the "
                         "configuration's: " + ", ".join(
                             f"{n} {have.get(n)} != {want.get(n)}"
                             for n in off))
    return jax.block_until_ready(jax.jit(
        lambda k: ref.init_params(k, config))(key))


def _new_request(st: State, traffic: dict):
    from repro.runtime import server as server_mod
    lo, hi = traffic["prompt_len"]
    req = server_mod.Request(
        uid=st.uid, prompt=st.rng.integers(
            0, st.cfg.vocab_size, int(st.rng.integers(lo, hi + 1))).astype(
                np.int32),
        max_new_tokens=traffic["new_tokens"])
    st.requests[st.uid] = req
    st.uid += 1
    st.server.submit(req)
    return req


def _tick(st: State, traffic: dict, span, log=None, refill: bool = True):
    """One server tick, then the closed loop's next requests. Returns the
    tokens served and the uids admitted."""
    with span("bench.tick"):
        st.server.tick()
    served, admitted = 0, []
    for e in st.server.step_log:
        served += int(np.sum(e["served"]))
        if e["kind"] == "admit":
            for slot, uid in zip(e["slots"], e["uids"]):
                if uid >= 0:
                    st.reused[uid] = bool(st.slot_uses[slot] > 0)
                    st.slot_uses[slot] += 1
                    admitted.append(uid)
    if log is not None:
        log.extend(st.server.step_log)
    if refill:
        with span("bench.admit"):
            for c, req in enumerate(st.clients):
                if req.status == "done":
                    st.clients[c] = _new_request(st, traffic)
    return served, admitted


def setup(ctx) -> State:
    _program()
    from repro import compat
    from repro.core import layout as layout_mod, quantize, retrieval
    from repro.models import lm
    from repro.runtime import server as server_mod

    c, tr = ctx.config, ctx.traffic
    cfg = model_config(c)
    t0 = time.perf_counter()
    codes = jax.block_until_ready(traffic_mod.draw_codes(
        ctx.key(2), ctx.key(0), c["rows"], c["code_bits"] // 32,
        {"kind": "uniform"}))
    values = jax.random.randint(ctx.key(5), (c["rows"],), 0, cfg.vocab_size,
                                jnp.int32)
    layout = jax.block_until_ready(layout_mod.build_layout(codes,
                                                           c["code_bits"]))
    t_store = time.perf_counter()
    params = load_params(c, cfg, ctx.key(6))
    t_weights = time.perf_counter()
    n, s = c["itq_sample_tokens"], c["itq_sample_len"]
    rows = c["slots"]
    toks = jax.random.randint(ctx.key(7), (n // s, s), 0, cfg.vocab_size,
                              jnp.int32)
    hidden = jax.jit(lambda p, t: lm.forward(p, cfg, t, return_hidden=True)[2])
    sample = jnp.concatenate([
        hidden(params, toks[i:i + rows]).reshape(-1, cfg.d_model).astype(
            jnp.float32) for i in range(0, n // s, rows)])
    itq = quantize.ITQParams(*ref.fit_itq(sample, c["code_bits"],
                                          c["itq_iters"], ctx.key(8)))
    del sample
    t_itq = time.perf_counter()
    store = retrieval.DataStore(codes=codes, values=values, itq=itq,
                                layout=layout)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    srv = server_mod.Server(cfg, mesh, params, max_batch=rows,
                            max_len=tr["prompt_len"][1] + tr["new_tokens"] + 1,
                            store=store)
    plan = srv.retrieval_plan.compact()
    missing = [p for p in c["expect_plan"] if p not in plan]
    if missing:
        raise RuntimeError(f"plan {plan} lacks {missing}")
    st = State(cfg=cfg, params=params, codes=codes, values=values, itq=itq,
               server=srv, rng=np.random.default_rng(
                   [int(ctx.seed) % (1 << 64), 3]),
               clients=[], requests={}, slot_uses=np.zeros(rows, np.int64),
               reused={})
    t_server = time.perf_counter()
    # one full cycle of the closed loop, then half of the next
    st.clients = [_new_request(st, tr) for _ in range(tr["clients"])]
    first = list(st.clients)
    while any(r.status != "done" for r in first):
        _tick(st, tr, _nospan)
    for _ in range(tr["new_tokens"] // 2):
        _tick(st, tr, _nospan)
    t_warm = time.perf_counter()
    print(f"setup: datastore and layout {t_store - t0:.3f} s, weights "
          f"{t_weights - t_store:.3f} s, itq {t_itq - t_weights:.3f} s, "
          f"server {t_server - t_itq:.3f} s, closed loop "
          f"{t_warm - t_server:.3f} s; plan {plan}",
          file=sys.stderr, flush=True)
    return st


def window(ctx, st: State, seconds: float, span) -> dict:
    """The closed loop for ``seconds``: ticks are sent while the window is
    open, the last counted to its end. A window stays open until it has
    admitted requests (the check samples those; warm-up puts the admission
    mid-window)."""
    tr = ctx.traffic
    log: List[dict] = []
    before = dict(st.server.counters)
    tokens, admitted, tick_s = 0, [], []
    with span("bench.window"):
        t0 = t = time.perf_counter()
        while not admitted or t - t0 < seconds:
            served, adm = _tick(st, tr, span, log)
            tokens += served
            admitted += adm
            now = time.perf_counter()
            tick_s.append(now - t)
            t = now
        last_s = t - t0
    med = float(np.median(tick_s))
    print(f"window: {len(tick_s)} ticks, median {med * 1e3:.3f} ms, max "
          f"{max(tick_s) * 1e3:.3f} ms, over 1.1x the median "
          f"{[round(x * 1e3, 3) for x in tick_s if x > 1.1 * med]} ms",
          file=sys.stderr, flush=True)
    after = st.server.counters
    counts = {k: after.get(k, 0) - before.get(k, 0)
              for k in ("decode_steps", "prefills", "prefill_tokens",
                        "retrieval_rows", "slot_state_resets",
                        "first_tokens_served")}
    return {"ticks": len(tick_s), "tokens": tokens, "last_s": last_s,
            "admitted": admitted, "log": log, "counts": counts}


def _rows_of(uid: int, log: List[dict]):
    """The served steps of request ``uid`` in order: (kind, served
    log-probs row, device outputs, row index)."""
    out = []
    for e in log:
        for a, (u, served) in enumerate(zip(e["uids"], e["served"])):
            if u == uid and served:
                out.append((e["kind"], e["logp"][a], e["outs"], a))
    return out


def check(ctx, st: State, rec: dict) -> dict:
    c, tr = ctx.config, ctx.traffic
    lim = c["limits"]
    log = rec["log"]
    # the sampled requests run to their end, outside the window
    rng = np.random.default_rng([int(ctx.seed) % (1 << 64), 4])
    pool = sorted(rec["admitted"])
    chosen = sorted(int(u) for u in rng.choice(
        pool, min(c["check_requests"], len(pool)), replace=False)) \
        if pool else []
    while any(st.requests[u].status != "done" for u in chosen):
        _tick(st, tr, _nospan, log, refill=False)
    reused = sum(st.reused.get(u, False) for u in chosen)
    if not chosen:          # no admission in the window: nothing checked
        return {n: (float("inf"), v) for n, v in lim.items()}

    missing, served_tokens = 0, []
    seqs, keys, codes, dists, ids, lm_logits, served = [], [], [], [], [], [], []
    probes, next_wkv = [], []
    for u in chosen:
        req = st.requests[u]
        steps = _rows_of(u, log)
        out = req.out_tokens
        if (not steps or steps[0][0] != "admit" or len(steps) != len(out)
                or len(out) != req.max_new_tokens):
            missing += 1
        for (kind, lp, outs, a), tok in zip(steps, out):
            served_tokens.append(tok)
            keys.append(outs["key"][a])
            codes.append(outs["codes"][a])
            dists.append(outs["dists"][a])
            ids.append(outs["ids"][a])
            lm_logits.append(outs["lm_logits"][a])
            served.append(lp)
        # the decode steps' probes, each with the state the next step read
        dec = [(outs["probe"], a) for _, _, outs, a in steps
               if "probe" in outs]
        for j, (pr, a) in enumerate(dec):
            probes.append({n: v[a] for n, v in pr.items()})
            next_wkv.append(dec[j + 1][0]["wkv"][dec[j + 1][1]]
                            if j + 1 < len(dec) else None)
        n = min(len(steps), len(out))
        seqs.append((np.concatenate([req.prompt, np.asarray(
            out[:max(n - 1, 0)], np.int32)]), len(req.prompt), n))
    probe = {n: jnp.stack([p[n] for p in probes]) for n in probes[0]} \
        if probes else {}
    has_next = jnp.asarray([w is not None for w in next_wkv], bool)
    if probes:
        probe["next_wkv"] = jnp.stack([
            jnp.zeros_like(probe["wkv"][0]) if w is None else w
            for w in next_wkv])
    key, q_codes = jnp.stack(keys), jnp.stack(codes)
    got_d, got_i = jnp.stack(dists), jnp.stack(ids)
    sys_lm = jnp.stack(lm_logits)
    served = jnp.stack(served)
    tokens_off = int(jnp.sum(jnp.argmax(served, axis=-1)
                             != jnp.asarray(served_tokens)))
    # the program goes before the reference runs: the server (compiled
    # steps, decode state, layout); the weights, codes and values are the
    # benchmark's own data
    st.server = None
    rec["log"] = log = None
    gc.collect()

    # stage 1: the LM against the float32 reference, teacher-forced
    T = max(len(s) for s, _, _ in seqs)
    toks = np.zeros((len(seqs), T), np.int32)
    for i, (s, _, _) in enumerate(seqs):
        toks[i, :len(s)] = s
    h_all = ref.lm_hidden(st.params, c, toks)
    h_ref = jnp.concatenate([h_all[i, p - 1:p - 1 + n]
                             for i, (_, p, n) in enumerate(seqs)])
    keyf = key.astype(jnp.float32)
    key_rel = jnp.linalg.norm(keyf - h_ref, axis=-1) / jnp.linalg.norm(
        h_ref, axis=-1)
    lm_err = jnp.max(jnp.abs(ref.lm_log_softmax(st.params, h_ref)
                             - jax.nn.log_softmax(sys_lm.astype(jnp.float32),
                                                  axis=-1)), axis=-1)
    # stage 1b: the last block and its recurrence from the step's own
    # inputs, without the layers below it
    if probe:
        last = jax.tree_util.tree_map(lambda a: a[-1], st.params["blocks"])
        x_in = probe["x_in"].astype(jnp.float32)
        d_ref = ref.block_out(last, x_in, probe["wkv"], probe["shift_t"],
                              probe["shift_c"], c) - x_in
        d_sys = probe["x_out"].astype(jnp.float32) - x_in
        block = jnp.linalg.norm(d_sys - d_ref, axis=-1) / jnp.linalg.norm(
            d_ref, axis=-1)
        y_err, s_err = ref.recurrence_err(
            probe["r"], probe["k"], probe["v"], probe["logw"], probe["y"],
            last["att"]["bonus"], probe["wkv"], probe["next_wkv"], has_next)
        wkv = jnp.maximum(y_err, s_err)
    else:
        block = wkv = jnp.zeros((0,), jnp.float32)
    # stage 2: query codes against the system's own key
    v, margin = ref.itq_project(keyf, st.itq.mean, st.itq.proj, st.itq.rot)
    sys_bits = ref.unpack_bits(q_codes, c["code_bits"])
    bits_off = jnp.sum((jnp.abs(v) > margin) & ((v > 0) != sys_bits), axis=-1)
    # stage 3: the search against the system's own codes
    ref_d, _ = hamming_topk.topk(st.codes, q_codes, c["k"])
    wrong = hamming_topk.wrong_rows(st.codes, q_codes, got_d, got_i, ref_d)
    # stage 4: the mix of the system's own LM logits and neighbours
    knn = ref.knn_log_probs(got_d, got_i, st.values, c["temperature"],
                            vocab=st.cfg.vocab_size)
    mixed = ref.interpolate(sys_lm, knn, c["interpolation"])
    mix = jnp.max(jnp.abs(served - mixed), axis=-1)

    key_rel, lm_err, bits_off, mix, block, wkv = (np.asarray(a) for a in (
        key_rel, lm_err, bits_off, mix, block, wkv))
    rec["failed_tokens"] = max(
        int(np.sum((lm_err > lim["lm_logp_err"])
                   | (key_rel > lim["key_rel_err"])
                   | (bits_off > lim["code_bits_off"])
                   | (mix > lim["mix_err"]))),
        int(np.sum((block > lim["block_err"]) | (wkv > lim["wkv_err"]))),
        int(wrong), tokens_off)
    return {
        "lm_logp_err": (float(lm_err.max(initial=0.0)), lim["lm_logp_err"]),
        "key_rel_err": (float(key_rel.max(initial=0.0)), lim["key_rel_err"]),
        "block_err": (float(block.max(initial=0.0)), lim["block_err"]),
        "wkv_err": (float(wkv.max(initial=0.0)), lim["wkv_err"]),
        "code_bits_off": (int(bits_off.sum()), lim["code_bits_off"]),
        "wrong_rows": (int(wrong), lim["wrong_rows"]),
        "mix_err": (float(mix.max(initial=0.0)), lim["mix_err"]),
        "tokens_off": (tokens_off, 0),
        "reused_short": (max(1 - reused, 0), lim["reused_short"]),
        "first_token_missing": (missing, lim["first_token_missing"]),
    }


def attempted_failed(rec: dict, checks: dict) -> tuple:
    return rec["tokens"], rec.get("failed_tokens", 0)


def end_to_end(ctx, rec: dict) -> dict:
    return {"search_qps": rec["tokens"] / rec["last_s"]}


def layer_inputs(ctx, rec: dict) -> dict:
    """``batches``: the window's search calls (decode steps and admission
    prefills, each a Q = ``slots`` search), as the store metrics read
    them."""
    n, c = rec["counts"], ctx.config
    return {"batches": n["decode_steps"] + n["prefills"],
            "least_time_per_batch_s": work.search_least_time(
                ctx.peaks, c["slots"], c["rows"], c["code_bits"]),
            "lm_least_time_s": lm_work.lm_least_time(
                ctx.peaks, ctx.config, decode_steps=n["decode_steps"],
                rows=ctx.config["slots"], prefill_tokens=n["prefill_tokens"],
                prefill_prompts=n["first_tokens_served"])}
