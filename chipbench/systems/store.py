"""A Hamming code store answering back-to-back query batches.

One chip: ``KNNEngine(codes, d, layout).search(q, k)`` under one
``jax.jit``, over a ``hamming_prefix`` layout built at set-up when the
configuration asks for one. Several chips (``shards`` > 1): the rows are
sharded over the chips and ``engine.search_sharded`` runs the distributed
select. The codes are drawn on the device from the seed, from the
distribution the configuration states, and the queries are held-out draws
from the same distribution (``harness/traffic.py``); the check compares a seed-drawn sample of every batch's answers with the
plain reference (``refs/hamming_topk.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Any, List

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness import traffic as traffic_mod, work
from refs import hamming_topk as ref


@dataclasses.dataclass
class State:
    codes: jax.Array
    pool: List[jax.Array]
    search: Any
    args: tuple


def make_codes(ctx, sharding=None) -> jax.Array:
    """The store's (rows, words) uint32 codes, made on the device(s)."""
    c = ctx.config
    return traffic_mod.draw_codes(ctx.key(2), ctx.key(0), c["rows"],
                                  c["code_bits"] // 32, c["codes"], sharding)


def make_queries(ctx, sharding=None) -> List[jax.Array]:
    """The query pool: held-out draws from the store's distribution."""
    c = ctx.config
    return traffic_mod.query_pool(ctx.key(2), ctx.key(1), c["code_bits"] // 32,
                                  c["codes"], ctx.traffic, sharding)


def setup(ctx) -> State:
    from repro.core import engine, plan as plan_mod

    c = ctx.config
    n, d, k, shards = c["rows"], c["code_bits"], c["k"], c["shards"]
    t0 = time.perf_counter()
    if shards == 1:
        codes = jax.block_until_ready(make_codes(ctx))
        t_codes = time.perf_counter()
        layout = None
        if c["layout"] == "hamming_prefix":
            layout = engine.KNNEngine(codes=codes, d=d).with_layout().layout
            jax.block_until_ready(layout)
        elif c["layout"] != "none":
            raise ValueError(f"unknown layout {c['layout']!r}")
        pool = make_queries(ctx)
        search = jax.jit(lambda cc, lo, q: engine.KNNEngine(
            codes=cc, d=d, layout=lo).search(q, k))
        args = (codes, layout)
        plan = engine.KNNEngine(codes=codes, d=d, layout=layout).query_plan(
            pool[0], k).compact()
    else:
        mesh = Mesh(np.asarray(ctx.devices[:shards]), ("data",))
        codes = jax.block_until_ready(make_codes(
            ctx, NamedSharding(mesh, P("data", None))))
        t_codes = time.perf_counter()
        pool = make_queries(ctx, NamedSharding(mesh, P()))
        search = jax.jit(lambda cc, q: engine.search_sharded(
            cc, q, k, d, mesh, ("data",)))
        args = (codes,)
        plan = plan_mod.plan_sharded(
            plan_mod.stats_of(codes, pool[0], d, n_shards=shards), k,
            axes=("data",)).compact()
    missing = [p for p in c["expect_plan"] if p not in plan]
    if missing:
        raise RuntimeError(f"plan {plan} lacks {missing}")
    t_built = time.perf_counter()
    jax.block_until_ready(search(*args, pool[0]))          # compile, warm
    t_warm = time.perf_counter() - t_built
    print(f"setup: codes {t_codes - t0:.3f} s, layout, queries and plan "
          f"{t_built - t_codes:.3f} s, first batch {t_warm:.3f} s; "
          f"plan {plan}", file=sys.stderr, flush=True)
    return State(codes=codes, pool=pool, search=search, args=args)


def window(ctx, st: State, seconds: float, span) -> dict:
    """Closed loop, one client: each batch ends in block_until_ready before
    the next is sent; batches are sent while the window is open."""
    outs, done = [], []
    with span("bench.window"):
        t0 = time.perf_counter()
        i = 0
        while i == 0 or time.perf_counter() - t0 < seconds:
            b = i % len(st.pool)
            with span("bench.batch"):
                out = jax.block_until_ready(st.search(*st.args, st.pool[b]))
            done.append(time.perf_counter() - t0)
            outs.append((b, out))
            i += 1
    return {"batches": i, "queries": i * ctx.traffic["batch"],
            "last_s": done[-1], "outs": outs}


def check(ctx, st: State, rec: dict) -> dict:
    """``wrong_rows``: sampled query rows whose answer is not an exact k-NN
    answer (``refs/hamming_topk.wrong_rows``); limit 0."""
    c = ctx.config
    q_per = ctx.traffic["batch"]
    rng = np.random.default_rng([int(ctx.seed) % (1 << 64), 2])
    qs, gd, gi = [], [], []
    for b, (dd, ii) in rec["outs"]:
        rows = np.sort(rng.choice(q_per, c["check_queries_per_batch"],
                                  replace=False))
        qs.append(st.pool[b][rows])
        gd.append(dd[rows])
        gi.append(ii[rows])
    q, got_d, got_i = (jnp.concatenate(a) for a in (qs, gd, gi))
    # the program's state (layout, compiled search) goes before the
    # reference runs; the codes and queries are the benchmark's own
    st.search = st.args = None
    rec["outs"] = None
    gc.collect()
    ref_d, _ = ref.topk(st.codes, q, c["k"], shards=c["shards"])
    wrong = int(ref.wrong_rows(st.codes, q, got_d, got_i, ref_d))
    return {"wrong_rows": (wrong, c["limits"]["wrong_rows"])}


def attempted_failed(rec: dict, checks: dict) -> tuple:
    return rec["queries"], int(checks["wrong_rows"][0])


def end_to_end(ctx, rec: dict) -> dict:
    return {"search_qps": rec["queries"] / rec["last_s"]}


def layer_inputs(ctx, rec: dict) -> dict:
    c = ctx.config
    rows_per_chip = c["rows"] // c["shards"]
    return {"batches": rec["batches"],
            "least_time_per_batch_s": work.search_least_time(
                ctx.peaks, ctx.traffic["batch"], rows_per_chip,
                c["code_bits"])}


def control(ctx, batches: int) -> dict:
    """The control: the reference put in the program's place with one
    guarantee broken. It searches all but the last 1/``control_skip_share``
    of the rows (a search that skips part of the store) and is compared,
    over ``batches`` batches sampled as ``check`` samples them, by the same
    number. It must come out not correct."""
    c = ctx.config
    n, k, shards = c["rows"], c["k"], c["shards"]
    sharding = None
    if shards > 1:
        mesh = Mesh(np.asarray(ctx.devices[:shards]), ("data",))
        sharding = NamedSharding(mesh, P("data", None))
    codes = make_codes(ctx, sharding)
    pool = make_queries(ctx)
    rng = np.random.default_rng([int(ctx.seed) % (1 << 64), 2])
    q_per = ctx.traffic["batch"]
    qs = [pool[b % len(pool)][np.sort(rng.choice(
        q_per, c["check_queries_per_batch"], replace=False))]
        for b in range(batches)]
    q = jnp.concatenate(qs)
    got_d, got_i = ref.topk(codes, q, k, shards=shards,
                            n_valid=n - n // c["control_skip_share"])
    ref_d, _ = ref.topk(codes, q, k, shards=shards)
    wrong = int(ref.wrong_rows(codes, q, got_d, got_i, ref_d))
    return {"wrong_rows": (wrong, c["limits"]["wrong_rows"])}
