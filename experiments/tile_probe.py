#!/usr/bin/env python3
"""On a TPU: how many pass-2 tiles the fused select runs on the store the
benchmark's cells search, what asking for the counts costs, and where the
``knn.*`` stages show in a device trace of one batch.

    python3 experiments/tile_probe.py --out DIR [--chips 1|4]
        [--batches 6] [--seed 7]

One chip: 2^26 uniform random 256-bit codes over a hamming_prefix layout,
searched by ``KNNEngine.search`` (the ``tagspace-d256`` configuration).
Four chips: 2^28 codes sharded 2^26 per chip, searched by
``engine.search_sharded`` through hist_merge (``tagspace-d256-x4``). Each
batch is 128 held-out uniform queries, k = 16. Every batch runs once with
``return_stats=False`` and once with ``True`` (alternating which goes
first), each timed to ``block_until_ready``; then one plain batch is traced,
and its device time is put under the ``knn.*`` scope that the compiled
program's op metadata gives each operation (as a profiler's name-scope view
does). Prints one JSON line per batch and a summary last; both are also
written to ``<out>/tile_probe_<chips>chip.json``. Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core import engine  # noqa: E402

D, K, Q, ROWS_PER_CHIP = 256, 16, 128, 1 << 26
_SCOPE = re.compile(r"knn\.[a-z0-9_.]+")
_INSTR = re.compile(r'^\s*(?:ROOT )?%([\w.-]+) = .*?op_name="([^"]*)"', re.M)


def _programs(chips: int, key):
    """(search(q, return_stats), compiled HLO text of search(q, False),
    query sharding) on codes drawn from key."""
    w = D // 32
    if chips == 1:
        codes = jax.jit(lambda kk: jax.random.bits(
            kk, (ROWS_PER_CHIP, w), jnp.uint32))(key)
        lay = engine.KNNEngine(codes=codes, d=D).with_layout().layout
        jax.block_until_ready(lay)
        fns = {rs: jax.jit(lambda cc, lo, q, rs=rs: engine.KNNEngine(
            codes=cc, d=D, layout=lo).search(q, K, return_stats=rs))
            for rs in (False, True)}
        return ((lambda q, rs: fns[rs](codes, lay, q)),
                (lambda q: fns[False].lower(codes, lay, q).compile().as_text()),
                None)
    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("data",))
    codes = jax.jit(lambda kk: jax.random.bits(
        kk, (chips * ROWS_PER_CHIP, w), jnp.uint32),
        out_shardings=NamedSharding(mesh, P("data", None)))(key)
    fns = {rs: jax.jit(lambda cc, q, rs=rs: engine.search_sharded(
        cc, q, K, D, mesh, ("data",), return_stats=rs))
        for rs in (False, True)}
    return ((lambda q, rs: fns[rs](codes, q)),
            (lambda q: fns[False].lower(codes, q).compile().as_text()),
            NamedSharding(mesh, P()))


def _scopes(hlo: str) -> dict:
    """{HLO instruction name: innermost knn.* scope of its op_name}."""
    out = {}
    for m in _INSTR.finditer(hlo):
        found = _SCOPE.findall(m.group(2))
        if found:
            out[m.group(1)] = found[-1]
    return out


def _stage_time(path: str, scope_of: dict) -> dict:
    """Device seconds of the traced batch per ``knn.*`` scope and per XLA
    operation (``XLA Ops`` line, mean over the chips), and the lines and
    event stats each device plane holds."""
    from jax.profiler import ProfileData

    stages, ops = collections.Counter(), collections.Counter()
    lines, planes = {}, 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        planes += 1
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = sorted({k for e in evs[:50] for k, _ in e.stats})
            if line.name != "XLA Ops":
                continue
            for ev in evs:
                name = ev.name.split(" = ")[0].strip().lstrip("%")
                stages[scope_of.get(name, "(no knn scope)")] += (
                    ev.duration_ns * 1e-9)
                ops[re.sub(r"(\.\d+)+$", "", name)] += ev.duration_ns * 1e-9
    mean = lambda c: {k: v / max(planes, 1) for k, v in c.most_common(12)}
    return {"device_planes": planes, "stage_s": mean(stages),
            "op_s": mean(ops), "lines": lines}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--batches", type=int, default=6)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", required=True,
                    help="directory for the summary and the trace")
    args = ap.parse_args(argv)
    dev = jax.devices()
    if dev[0].platform != "tpu" or len(dev) < args.chips:
        print(f"needs {args.chips} TPU chip(s); found {dev}", file=sys.stderr)
        return 2

    key_codes, key_q = jax.random.split(jax.random.PRNGKey(args.seed))
    t0 = time.perf_counter()
    search, hlo, q_sharding = _programs(args.chips, key_codes)
    queries = jax.random.bits(key_q, (args.batches, Q, D // 32), jnp.uint32)
    if q_sharding is not None:
        queries = jax.device_put(queries, q_sharding)
    for rs in (False, True):                     # compile and warm both
        jax.block_until_ready(search(queries[0], rs))
    setup_s = time.perf_counter() - t0

    rows, secs = [], {False: [], True: []}
    for b in range(args.batches):
        q = queries[b]
        outs = {}
        for rs in ((False, True) if b % 2 == 0 else (True, False)):
            t = time.perf_counter()
            outs[rs] = jax.block_until_ready(search(q, rs))
            secs[rs].append(time.perf_counter() - t)
        st = jax.device_get(outs[True][2])
        same = bool((np.asarray(outs[False][0]) == np.asarray(outs[True][0])).all()
                    and (np.asarray(outs[False][1])
                         == np.asarray(outs[True][1])).all())
        row = {"batch": b, "blocks_total": int(st["blocks_total"]),
               "blocks_skipped": int(st["blocks_skipped"]),
               "p1_blocks_skipped": int(st["p1_blocks_skipped"]),
               "p1_fine_blocks_skipped": int(st["p1_fine_blocks_skipped"]),
               "same_answers": same, "plain_s": secs[False][-1],
               "stats_s": secs[True][-1]}
        if "shard_blocks_skipped" in st:
            row["shard_blocks_skipped"] = np.asarray(
                st["shard_blocks_skipped"]).tolist()
        rows.append(row)
        print(json.dumps(row), flush=True)

    tmp = tempfile.mkdtemp(prefix="tile-probe-")
    with jax.profiler.trace(tmp):
        jax.block_until_ready(search(queries[0], False))
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    trace = _stage_time(found[0], _scopes(hlo(queries[0]))) if found else {}
    os.makedirs(args.out, exist_ok=True)
    if found:
        shutil.copy(found[0], os.path.join(
            args.out, f"tile_probe_{args.chips}chip.xplane.pb"))

    run = [r["blocks_total"] - r["blocks_skipped"] for r in rows]
    summary = {
        "chips": args.chips, "device_kind": dev[0].device_kind,
        "setup_s": setup_s, "tiles_per_batch": rows[0]["blocks_total"],
        "pass2_tiles_run": run,
        "pass2_run_share_min": min(run) / rows[0]["blocks_total"],
        "plain_s_median": statistics.median(secs[False]),
        "stats_s_median": statistics.median(secs[True]),
        "stats_cost_share": (statistics.median(secs[True])
                             / statistics.median(secs[False]) - 1),
        "same_answers": all(r["same_answers"] for r in rows),
        "trace": trace}
    with open(os.path.join(args.out,
                           f"tile_probe_{args.chips}chip.json"), "w") as f:
        json.dump({"batches": rows, "summary": summary}, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
