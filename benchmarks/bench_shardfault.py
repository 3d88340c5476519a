"""Kill-shards-mid-soak harness for the shard-fault-tolerance layer
(dist/search.py + dist/health.py + dist/sharding.ReplicaMap).

A seeded query stream runs against a 4-unit FaultTolerantSearch while
units are hard-killed mid-stream at a configurable probability per tick
(and revived/re-replicated in the background), with the low-rate
``shard_hist``/``shard_emit``/``merge_psum`` injected faults on top.
EVERY answer is checked against the from-scratch reference over exactly
the rows its CoverageReport claims were searched — the two invariants the
soak exists to pin:

1. zero lost requests: every query returns an answer, degraded or not;
2. coverage is never silently mis-reported: the answer is bit-identical
   (dists AND ids) to ``ops.hamming_topk`` over precisely
   ``covered_rows`` rows, never fewer, never more.

Separate scenario rows pin the rest of the acceptance surface: with
replication factor 2 a double-kill degrades exactly one range and
coverage returns to 1.0 after re-replication; the hierarchical host merge
is bit-identical across fanouts (tree == flat); and an in-process SPMD row
over four devices (four host devices on the CPU) pins hist_tree ==
hist_merge == single-device reference through the jitted
``engine.search_sharded`` path.

Standalone CLI (what CI's shardfault-soak-smoke job runs):
    PYTHONPATH=src python benchmarks/bench_shardfault.py \
        --ticks 150 --kill-p 0.05 --json BENCH_shardfault.json
Exit code is non-zero if any invariant breaks. Also registered in
benchmarks/run.py (tag ``shardfault``) with a short, SPMD-free preset.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

COUNTS = (300, 512, 11, 201)     # deliberately uneven: unit2 is tiny
D = 64


def _corpus(seed: int):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 2 ** 32, (sum(COUNTS), D // 32), dtype=np.uint32)
    return rng, codes


def kill_soak(*, ticks: int, kill_p: float, revive_p: float, factor: int,
              seed: int = 0, k: int = 16, q_batch: int = 4,
              fault_p: float = 0.01) -> dict:
    """The mid-stream kill soak: returns the verified stats row."""
    from repro.dist.search import FaultTolerantSearch, reference_over_covered
    from repro.runtime import faults as faults_mod

    rng, codes = _corpus(seed)
    inj = faults_mod.FaultInjector(
        seed=seed + 1, p={"shard_hist": fault_p, "shard_emit": fault_p,
                          "merge_psum": fault_p})
    # generous per-call deadline: the soak's kills are explicit; the
    # deadline-driven suspect/dead walk is pinned in tests/test_shard_faults
    fts = FaultTolerantSearch(codes, D, counts=list(COUNTS), factor=factor,
                              injector=inj, deadline_s=5.0)
    row = {"ticks": ticks, "kill_p": kill_p, "revive_p": revive_p,
           "factor": factor, "submitted": 0, "answered": 0, "lost": 0,
           "mismatches": 0, "coverage_misreports": 0, "degraded_answers": 0,
           "kills": 0, "revives": 0, "coverage_min": 1.0}
    t0 = time.perf_counter()
    for _t in range(ticks):
        if rng.random() < kill_p:
            serving = sorted(fts.registry.serving())
            if serving:
                fts.kill(serving[int(rng.integers(len(serving)))])
                row["kills"] += 1
        if rng.random() < revive_p:
            dead = sorted(fts.registry.dead())
            if dead:
                # factor>1 can refill a cold (wiped) unit from replicas;
                # factor 1 has no second copy, so revive warm
                cold = factor > 1 and bool(rng.integers(2))
                fts.revive(dead[int(rng.integers(len(dead)))],
                           with_data=not cold)
                row["revives"] += 1
        q = rng.integers(0, 2 ** 32, (q_batch, D // 32), dtype=np.uint32)
        row["submitted"] += 1
        try:
            dd, ii, rep = fts.search(q, k)
        except Exception:  # noqa: BLE001 — a lost request is the failure
            row["lost"] += 1
            continue
        row["answered"] += 1
        m = fts.covered_row_ids()
        if rep.covered_rows != m.size:
            row["coverage_misreports"] += 1
        rd, ri = reference_over_covered(codes, q, k, D, m)
        if not (np.array_equal(dd, rd) and np.array_equal(ii, ri)):
            row["mismatches"] += 1
        if not rep.complete:
            row["degraded_answers"] += 1
        row["coverage_min"] = min(row["coverage_min"], rep.coverage_frac)
        fts.maintain(budget=1)
    wall = time.perf_counter() - t0
    row.update(fts.counters)
    row["wall_s"] = wall
    row["qps"] = row["answered"] / max(wall, 1e-9)
    row["injected"] = {s: n for s, n in inj.fired.items()}
    row["ok"] = (row["lost"] == 0 and row["mismatches"] == 0
                 and row["coverage_misreports"] == 0)
    return row


def replication_scenario(seed: int = 0, k: int = 16) -> dict:
    """R=2 acceptance row: a double-kill loses exactly one range
    (degraded-but-exact), and re-replication returns coverage to 1.0."""
    from repro.dist.search import FaultTolerantSearch, reference_over_covered

    rng, codes = _corpus(seed)
    q = rng.integers(0, 2 ** 32, (5, D // 32), dtype=np.uint32)
    N = codes.shape[0]
    fts = FaultTolerantSearch(codes, D, counts=list(COUNTS), factor=2,
                              deadline_s=5.0)
    row = {"factor": 2, "ok": True}

    # one kill: the replica serves, coverage stays 1.0
    fts.kill("unit1")
    dd, ii, rep = fts.search(q, k)
    rd, ri = reference_over_covered(codes, q, k, D, np.arange(N))
    row["single_kill_exact"] = bool(np.array_equal(dd, rd)
                                    and np.array_equal(ii, ri))
    row["single_kill_coverage"] = rep.coverage_frac

    # second kill takes range 1's last holder: degraded, still exact
    fts.kill("unit2")
    dd, ii, rep = fts.search(q, k)
    m = fts.covered_row_ids()
    rd, ri = reference_over_covered(codes, q, k, D, m)
    row["double_kill_exact"] = bool(np.array_equal(dd, rd)
                                    and np.array_equal(ii, ri))
    row["double_kill_coverage"] = rep.coverage_frac
    row["double_kill_dead"] = list(rep.dead_shards)

    # warm revive + background re-replication: coverage returns to 1.0
    fts.revive("unit1", with_data=True)
    m1 = fts.maintain()
    dd, ii, rep = fts.search(q, k)
    rd, ri = reference_over_covered(codes, q, k, D, np.arange(N))
    row["recovered_exact"] = bool(np.array_equal(dd, rd)
                                  and np.array_equal(ii, ri))
    row["recovered_coverage"] = rep.coverage_frac
    row["rebuilt_ranges"] = m1["copied"]
    row["ok"] = (row["single_kill_exact"] and row["double_kill_exact"]
                 and row["single_kill_coverage"] == 1.0
                 and abs(row["double_kill_coverage"]
                         - (N - COUNTS[1]) / N) < 1e-9
                 and row["recovered_exact"]
                 and row["recovered_coverage"] == 1.0)
    return row


def merge_identity(seed: int = 0, k: int = 16) -> dict:
    """Healthy fleet: the hierarchical host merge is bit-identical across
    every fanout (tree schedules == the flat single-group sum)."""
    from repro.dist.search import FaultTolerantSearch, reference_over_covered

    rng, codes = _corpus(seed)
    q = rng.integers(0, 2 ** 32, (5, D // 32), dtype=np.uint32)
    rd, ri = reference_over_covered(codes, q, k, D,
                                    np.arange(codes.shape[0]))
    row = {"fanouts": [], "ok": True}
    for fanout in (2, 3, 4):     # 4 units: fanout 4 IS the flat merge
        fts = FaultTolerantSearch(codes, D, counts=list(COUNTS),
                                  fanout=fanout, deadline_s=5.0)
        dd, ii, rep = fts.search(q, k)
        same = bool(np.array_equal(dd, rd) and np.array_equal(ii, ri)
                    and rep.complete)
        row["fanouts"].append({"fanout": fanout, "identical": same})
        row["ok"] = row["ok"] and same
    return row


def spmd_identity(devices) -> dict:
    """hist_tree == hist_merge == single-device reference through the
    jitted SPMD ``engine.search_sharded`` path, in this process, over four
    of its devices."""
    import warnings

    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core import binary, engine
    from repro.kernels import ops

    rng = np.random.default_rng(11)
    d, N, Q, k = 64, 2048, 8, 16
    xp = binary.pack_bits(jnp.asarray(rng.integers(0, 2, (N, d)), jnp.uint8))
    qp = binary.pack_bits(jnp.asarray(rng.integers(0, 2, (Q, d)), jnp.uint8))
    mesh = Mesh(np.array(devices[:4]), ("data",))
    rd, ri = ops.hamming_topk(qp, xp, k, d + 1)
    with mesh:
        hd, hi = engine.search_sharded(xp, qp, k, d, mesh, ("data",))
        td, ti = engine.search_sharded(xp, qp, k, d, mesh, ("data",),
                                       merge="hist_tree", fanout=2)
    part = jnp.asarray(np.array([1, 0, 1, 1], np.int32))
    surv = jnp.asarray(np.concatenate([np.asarray(xp)[:512],
                                       np.asarray(xp)[1024:]]))
    rd2, ri2 = ops.hamming_topk(qp, surv, k, d + 1)
    with mesh, warnings.catch_warnings():
        warnings.simplefilter("ignore")
        md, mi = engine.search_sharded(xp, qp, k, d, mesh, ("data",),
                                       merge="hist_tree", fanout=2,
                                       shard_participate=part)
    checks = {
        "hist_merge_eq_reference": bool((hd == rd).all() and (hi == ri).all()),
        "hist_tree_eq_hist_merge": bool((td == hd).all() and (ti == hi).all()),
        "masked_tree_eq_rebuild": bool((md == rd2).all()
                                       and (mi == ri2).all()),
    }
    return {"ok": all(checks.values()), **checks}


def _report_rows(rows: dict, report) -> None:
    for name, r in rows.items():
        if name.startswith("soak"):
            derived = (f"ok={r['ok']};kills={r['kills']};"
                       f"revives={r['revives']};lost={r['lost']};"
                       f"mismatches={r['mismatches']};"
                       f"degraded_answers={r['degraded_answers']};"
                       f"coverage_min={r['coverage_min']:.3f};"
                       f"failovers={r['failovers']};qps={r['qps']:.1f}")
            us = r["wall_s"] * 1e6 / max(r["answered"], 1)
        else:
            derived = f"ok={r['ok']}"
            us = 0.0
        report(f"shardfault_{name},{us:.1f},{derived}")


def run(report):
    """benchmarks/run.py hook — short preset, host-level only (the SPMD
    row is CI's standalone invocation)."""
    rows = {
        "soak_r1": kill_soak(ticks=40, kill_p=0.05, revive_p=0.15, factor=1),
        "soak_r2": kill_soak(ticks=40, kill_p=0.05, revive_p=0.15, factor=2),
        "replication": replication_scenario(),
        "merge_identity": merge_identity(),
    }
    _report_rows(rows, report)
    if not all(r["ok"] for r in rows.values()):
        raise RuntimeError("shardfault invariants violated")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=150)
    ap.add_argument("--kill-p", type=float, default=0.05)
    ap.add_argument("--revive-p", type=float, default=0.15)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-spmd", action="store_true",
                    help="skip the 4-device SPMD identity row")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_shardfault.json-style output to PATH")
    args = ap.parse_args()

    rows = {
        "soak_r1": kill_soak(ticks=args.ticks, kill_p=args.kill_p,
                             revive_p=args.revive_p, factor=1,
                             seed=args.seed),
        "soak_r2": kill_soak(ticks=args.ticks, kill_p=args.kill_p,
                             revive_p=args.revive_p, factor=2,
                             seed=args.seed),
        "replication": replication_scenario(seed=args.seed),
        "merge_identity": merge_identity(seed=args.seed),
    }
    if not args.no_spmd:
        import jax

        devices = jax.devices()
        if len(devices) >= 4:
            rows["spmd_identity"] = spmd_identity(devices)
        else:
            print(f"spmd_identity row skipped: it needs 4 devices, this "
                  f"process has {len(devices)}", file=sys.stderr)

    print("name,us_per_call,derived")
    _report_rows(rows, lambda line: print(line, flush=True))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "shardfault", "counts": list(COUNTS),
                       "ticks": args.ticks, "kill_p": args.kill_p,
                       "rows": rows}, f, indent=1)
        print(f"wrote {len(rows)} rows to {args.json}", file=sys.stderr)

    bad = [n for n, r in rows.items() if not r["ok"]]
    if bad:
        print(f"SHARD-FAULT INVARIANTS VIOLATED: {bad}", file=sys.stderr)
        raise SystemExit(1)
    print("all shard-fault invariants held", file=sys.stderr)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    # four host devices for the SPMD row when the backend is the CPU; read
    # once when jax initializes, ignored by an accelerator backend
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    main()
