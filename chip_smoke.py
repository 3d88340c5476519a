#!/usr/bin/env python3
"""Chip smoke: the search store and the kNN-LM server, once each, on a TPU,
at a size users run, checked against a plain jax.numpy reference.

    python3 chip_smoke.py               # one chip: store + served phases
    python3 chip_smoke.py --chips 4     # four chips: the sharded phase only

Everything runs in this one process (a chip belongs to one process).

* store phase: N = 2^26 random 256-bit codes (2 GiB), made on the device
  from ``--seed``; Q = 4096 queries, k = 16 (the kNN-TagSpace row of
  benchmarks/bench_workloads.py). Half the queries are near copies of
  stored rows, half are random. ``KNNEngine.search`` runs twice, compiled
  under ``jax.jit``: on the plain engine, where ``auto`` resolves to the
  XLA composite path, and on ``KNNEngine.with_layout()`` (hamming_prefix),
  where it resolves to the fused Pallas kernels — whose compiled program
  must hold a ``tpu_custom_call`` (no interpret mode). Both are checked
  exactly, dists and ids, on every 16th query against
  ``reference_topk`` (ties broken by index). The layout engine scans rows
  in layout order, so its ids are checked against the reference over
  ``layout.codes`` mapped back through ``layout.perm``; its dists against
  the reference over the original rows.
* served phase: ``runtime.server.Server`` answers 8 requests of 16 new
  tokens on rwkv6-1.6b at full width (random weights from ``--seed``),
  with a 2^20-entry retrieval datastore bucketed by hamming_prefix, so
  every decode step runs the fused kernels at Q = 8. No degradation
  policy: every request must complete with zero failover ticks, so the
  failover ladder cannot hide a failing kernel. The served store's search
  is checked against the reference at Q = 8 as well.
* ``--chips 4``: N = 2^28 rows sharded over four chips (2 GiB per chip);
  ``engine.search_sharded`` (the hist_merge distributed counting select)
  is checked against the reference run under jit over the same sharded
  array, and each device must hold N/4 rows.

Earlier lines report each phase (timings are smoke timings: one compiled
call, timed to ``block_until_ready``). The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or outside a checkout of this repository, the script exits
non-zero and prints no result: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

D, K = 256, 16                      # kNN-TagSpace: 256-bit codes, k = 16
STORE_ROWS, STORE_QUERIES = 1 << 26, 4096
SHARDED_ROWS = 1 << 28
REF_EVERY = 16                      # reference checks every 16th query
REF_CHUNK = 1 << 16
SERVE_ARCH = "rwkv6-1.6b"
SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW_TOKENS = 8, 8, 16


def report(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def timed(fn, *args):
    """(compiled fn, compile s, result, run s) for ``jax.jit(fn)(*args)``."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return compiled, t1 - t0, out, time.perf_counter() - t1


# ---------------------------------------------------------------------------
# data and the plain reference (independent of the package under test)
# ---------------------------------------------------------------------------

def make_codes(key, n: int, d: int, sharding=None) -> jax.Array:
    """(n, d/32) uint32 random codes, generated on the device(s)."""
    return jax.jit(lambda k: jax.random.bits(k, (n, d // 32), jnp.uint32),
                   out_shardings=sharding)(key)


@functools.partial(jax.jit, static_argnames=("q",))
def make_queries(key, codes, q: int):
    """Even rows: a stored row with each bit flipped at p = 1/16; odd rows:
    random codes."""
    k1, k2, k3 = jax.random.split(key, 3)
    w = codes.shape[1]
    ids = jax.random.randint(k1, (q,), 0, codes.shape[0])
    b = jax.random.bits(k2, (4, q, w), jnp.uint32)
    near = codes[ids] ^ (b[0] & b[1] & b[2] & b[3])
    rand = jax.random.bits(k3, (q, w), jnp.uint32)
    return jnp.where((jnp.arange(q) % 2 == 0)[:, None], near, rand)


@functools.partial(jax.jit, static_argnames=("k", "shards", "chunk"))
def reference_topk(codes, queries, k: int, shards: int = 1,
                   chunk: int = REF_CHUNK):
    """Exact top-k Hamming neighbours in plain jax.numpy: ascending
    distance, ties broken by row index. The rows split into ``shards``
    contiguous slices (the mesh's row sharding) scanned in ``chunk``-row
    steps, so a sharded ``codes`` is read where it lives."""
    n, w = codes.shape
    chunk = min(chunk, n // shards)
    per = n // shards // chunk
    assert per * shards * chunk == n, (n, shards, chunk)
    x = codes.reshape(shards, per, chunk, w)
    nq = queries.shape[0]
    local = jnp.arange(chunk, dtype=jnp.int32)
    base = (jnp.arange(shards, dtype=jnp.int32) * (per * chunk))[:, None, None]

    def step(carry, c):
        best_d, best_i = carry                                  # (S, Qs, k)
        xc = x[:, c]                                            # (S, chunk, W)
        dist = jnp.sum(jax.lax.population_count(
            queries[None, :, None, :] ^ xc[:, None, :, :]).astype(jnp.int32),
            axis=-1)                                           # (S, Qs, chunk)
        neg, _ = jax.lax.top_k(-(dist * chunk + local), k)      # unique keys
        cd, ci = (-neg) // chunk, (-neg) % chunk + c * chunk + base
        dd = jnp.concatenate([best_d, cd], axis=-1)
        ii = jnp.concatenate([best_i, ci], axis=-1)
        dd, ii = jax.lax.sort((dd, ii), num_keys=2)
        return (dd[..., :k], ii[..., :k]), None

    big = jnp.full((shards, nq, k), jnp.iinfo(jnp.int32).max, jnp.int32)
    (bd, bi), _ = jax.lax.scan(step, (big, big), jnp.arange(per))
    dd = bd.transpose(1, 0, 2).reshape(nq, shards * k)
    ii = bi.transpose(1, 0, 2).reshape(nq, shards * k)
    dd, ii = jax.lax.sort((dd, ii), num_keys=2)
    return dd[:, :k], ii[:, :k]


def matches(got, want) -> int:
    """Number of query rows on which ``got`` equals ``want`` exactly."""
    return int(np.sum(np.all(np.asarray(got) == np.asarray(want), axis=1)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def store_phase(seed: int, n: int = STORE_ROWS, q: int = STORE_QUERIES,
                k: int = K, d: int = D, chunk: int = REF_CHUNK) -> dict:
    from repro.core import engine

    kc, kq = jax.random.split(jax.random.PRNGKey(seed))
    codes = make_codes(kc, n, d)
    queries = make_queries(kq, codes, q)
    sub = queries[::REF_EVERY]
    ref_d, ref_i = reference_topk(codes, sub, k, chunk=chunk)
    out = {}

    plain = engine.KNNEngine(codes=codes, d=d)
    plan = plain.query_plan(queries, k).compact()
    assert "select:composite" in plan, plan
    _, c_s, (dd, ii), run_s = timed(
        lambda c, qq: engine.KNNEngine(codes=c, d=d).search(qq, k),
        codes, queries)
    out["composite"] = dict(
        plan=plan, compile_s=c_s, smoke_search_s=run_s,
        match_dists=matches(dd[::REF_EVERY], ref_d),
        match_ids=matches(ii[::REF_EVERY], ref_i), checked=sub.shape[0],
        peak_bytes_in_use=peak_bytes())
    del dd, ii

    t0 = time.perf_counter()
    lay_eng = plain.with_layout()
    lay = jax.block_until_ready(lay_eng.layout)
    build_s = time.perf_counter() - t0
    assert bool(jnp.all(codes[lay.perm] == lay.codes)), "layout != codes[perm]"
    plan = lay_eng.query_plan(queries, k).compact()
    assert "select:fused" in plan, plan
    compiled, c_s, (dd, ii), run_s = timed(
        lambda c, lo, qq: engine.KNNEngine(codes=c, d=d, layout=lo).search(
            qq, k), codes, lay, queries)
    _, lay_pos = reference_topk(lay.codes, sub, k, chunk=chunk)
    out["fused"] = dict(
        plan=plan, layout_build_s=build_s, compile_s=c_s,
        smoke_search_s=run_s,
        match_dists=matches(dd[::REF_EVERY], ref_d),
        match_ids=matches(ii[::REF_EVERY], lay.perm[lay_pos]),
        checked=sub.shape[0],
        tpu_custom_call="tpu_custom_call" in compiled.as_text(),
        peak_bytes_in_use=peak_bytes())
    return out


def served_phase(seed: int, cfg=None, n_requests: int = SERVE_REQUESTS,
                 prompt_len: int = SERVE_PROMPT,
                 new_tokens: int = SERVE_NEW_TOKENS) -> dict:
    import dataclasses

    from repro import compat
    from repro.configs import get_config
    from repro.core import engine, retrieval
    from repro.dist import sharding
    from repro.models import lm
    from repro.runtime import server

    cfg = cfg if cfg is not None else get_config(SERVE_ARCH)
    cfg = dataclasses.replace(cfg, retrieval=dataclasses.replace(
        cfg.retrieval, layout="hamming_prefix"))
    rcfg = cfg.retrieval
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    t0 = time.perf_counter()
    with mesh:
        params = jax.jit(
            lambda: lm.init_params(jax.random.PRNGKey(seed), cfg),
            out_shardings=sharding.named(mesh, sharding.param_specs(cfg)))()
    store = retrieval.synthetic_datastore(cfg,
                                          key=jax.random.PRNGKey(seed + 1))
    store = jax.device_put(store, sharding.named(
        mesh, sharding.datastore_specs(mesh, store)))
    srv = server.Server(cfg, mesh, params, max_batch=n_requests,
                        max_len=prompt_len + new_tokens + 1, store=store)
    init_s = time.perf_counter() - t0
    plan = srv.retrieval_plan.compact()
    assert "select:fused" in plan and "prebuilt" in plan, plan

    # the served store's search at decode-sized Q, against the reference
    qk = jax.random.PRNGKey(seed + 2)
    qq = make_queries(qk, store.codes, n_requests)
    dd, ii = jax.jit(lambda c, lo, x: engine.KNNEngine(
        codes=c, d=rcfg.code_bits, layout=lo).search(x, rcfg.k))(
            store.codes, store.layout, qq)
    chunk = min(REF_CHUNK, store.codes.shape[0])
    ref_d, _ = reference_topk(store.codes, qq, rcfg.k, chunk=chunk)
    _, lay_pos = reference_topk(store.layout.codes, qq, rcfg.k, chunk=chunk)

    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n_requests, prompt_len)).astype(np.int32)
    for uid in range(n_requests):
        assert srv.submit(server.Request(uid=uid, prompt=prompts[uid],
                                         max_new_tokens=new_tokens))
    t0 = time.perf_counter()
    ticks = srv.run(max_ticks=4 * (prompt_len + new_tokens) * n_requests)
    run_s = time.perf_counter() - t0
    s = srv.stats()
    tokens = [t for r in srv.done for t in r.out_tokens]
    return dict(
        arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
        datastore=int(store.codes.shape[0]), plan=plan, init_s=init_s,
        smoke_run_s=run_s, ticks=ticks, done=s["done"], shed=s["shed"],
        timed_out=s["timed_out"], lost=s["lost"],
        failover_ticks=s["failover_ticks"],
        search_failures=s["search_failures"],
        complete=sum(len(r.out_tokens) == new_tokens for r in srv.done),
        tokens_in_vocab=all(0 <= t < cfg.vocab_size for t in tokens),
        match_dists=matches(dd, ref_d),
        match_ids=matches(ii, store.layout.perm[lay_pos]),
        checked=n_requests, peak_bytes_in_use=peak_bytes())


def sharded_phase(seed: int, devices, n: int = SHARDED_ROWS,
                  q: int = STORE_QUERIES, k: int = K, d: int = D,
                  chunk: int = REF_CHUNK) -> dict:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import engine, plan as plan_mod

    n_dev = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    kc, kq = jax.random.split(jax.random.PRNGKey(seed))
    codes = make_codes(kc, n, d, sharding=rows)
    held = {s.device.id: s.data.shape[0] for s in codes.addressable_shards}
    assert len(held) == n_dev and set(held.values()) == {n // n_dev}, held
    queries = make_queries(kq, codes, q)
    sub = queries[::REF_EVERY]
    plan = plan_mod.plan_sharded(
        plan_mod.stats_of(codes, queries, d, n_shards=n_dev), k,
        axes=("data",)).compact()
    assert "select:fused" in plan and "hist_merge" in plan, plan
    _, c_s, (dd, ii), run_s = timed(
        lambda c, qq: engine.search_sharded(c, qq, k, d, mesh, ("data",)),
        codes, queries)
    ref_d, ref_i = reference_topk(codes, sub, k, shards=n_dev, chunk=chunk)
    return dict(plan=plan, n_devices=n_dev, rows_per_device=n // n_dev,
                compile_s=c_s, smoke_search_s=run_s,
                match_dists=matches(dd[::REF_EVERY], ref_d),
                match_ids=matches(ii[::REF_EVERY], ref_i),
                checked=sub.shape[0], peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def _fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def _check_exact(name: str, r: dict) -> None:
    for key in ("match_dists", "match_ids"):
        if r[key] != r["checked"]:
            _fail(f"{name}: {key}={r[key]} of {r['checked']} query rows")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        from repro.launch import cache
    except ImportError as e:
        _fail(f"run from a checkout of the repository ({e})", 2)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _fail(f"no accelerator: {e}", 2)
    if devices[0].platform != "tpu":
        _fail(f"needs a TPU; JAX found {devices[0].platform}", 2)
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} but JAX found {len(devices)}", 2)
    report(phase="setup", compile_cache=cache.enable_compile_cache(),
           platform=devices[0].platform, kind=repr(devices[0].device_kind),
           count=len(devices))

    if args.chips == 4:
        r = sharded_phase(args.seed, devices[:4])
        report(phase="sharded", N=SHARDED_ROWS, Q=STORE_QUERIES, k=K, **r)
        _check_exact("sharded", r)
    else:
        res = store_phase(args.seed)
        for path, r in res.items():
            report(phase=f"store/{path}", N=STORE_ROWS, Q=STORE_QUERIES,
                   k=K, **r)
            _check_exact(f"store/{path}", r)
        if not res["fused"]["tpu_custom_call"]:
            _fail("the fused search compiled without a tpu_custom_call")
        r = served_phase(args.seed)
        report(phase="served", **r)
        _check_exact("served", r)
        bad = {key: r[key] for key in ("shed", "timed_out", "lost",
                                       "failover_ticks", "search_failures")
               if r[key]}
        if (bad or r["done"] != SERVE_REQUESTS
                or r["complete"] != SERVE_REQUESTS
                or not r["tokens_in_vocab"]):
            _fail(f"served: done={r['done']} complete={r['complete']} "
                  f"tokens_in_vocab={r['tokens_in_vocab']} {bad}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
