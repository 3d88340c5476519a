#!/usr/bin/env python3
"""On a TPU: pass 1 of the fused select (the histogram race) in one level
against two levels, over the widths the store runs.

    python3 experiments/race_levels.py --out DIR [--widths 64,128,256]
        [--shifts 0,2,3,4,5] [--reps 3] [--seed 7]

For each width d: N = 2^26 uniform random d-bit codes and Q = 128 held-out
uniform queries, k = 16, at the grid the fused select picks for that shape
(``ops.topk_geometry``). Each mode is one jitted program, pass 1 and the
radius it derives (``ops._race``): shift 0 is the one-level race over all
d + 1 bins, shift s > 0 the two-level race with a 2^s-distance window.
Each program is compiled and run once, then timed ``--reps`` times to
``block_until_ready`` (the median is kept), and its r*, n_lt and n_emit
must equal the one-level race's. ``tuning.race_shift`` takes its
threshold and shift from these numbers. Prints one JSON line per (width,
mode) and a summary last; both are also written to
``<out>/race_levels.json``. Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.kernels import ops, tuning  # noqa: E402
from repro.kernels.topk_select import hamming_hist_pallas  # noqa: E402

K, Q, N = 16, 128, 1 << 26


def _race_program(bins: int, shift: int):
    """(q, x) -> (r*, n_lt, n_emit, fine-level tiles run or -1)."""
    @jax.jit
    def run(q, x):
        qp, xp, bq, bn, sub = ops._topk_blocked(q, x, max(bins, K),
                                                None, None, None)
        race = ops._race(lambda **kw: hamming_hist_pallas(
            qp, xp, bins, bq=bq, bn=bn, sub=sub, return_tiles=True, **kw),
            Q, K, bins, shift)
        fine = (jnp.int32(-1) if race.fine_tiles is None
                else race.fine_tiles)
        return race.r_star, race.n_lt, race.n_emit, fine
    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--widths", default="64,128,256")
    ap.add_argument("--shifts", default="0,2,3,4,5",
                    help="modes to time; 0 is the one-level race")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", required=True, help="directory for the JSON")
    args = ap.parse_args(argv)
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"needs a TPU chip; found {dev}", file=sys.stderr)
        return 2

    shifts = sorted({0} | {int(s) for s in args.shifts.split(",")})
    rows = []
    for d in (int(w) for w in args.widths.split(",")):
        bins, w = d + 1, d // 32
        key_x, key_q = jax.random.split(jax.random.PRNGKey(args.seed + d))
        x = jax.jit(lambda kk: jax.random.bits(kk, (N, w), jnp.uint32))(key_x)
        q = jax.random.bits(key_q, (Q, w), jnp.uint32)
        bq, bn, sub, q_pad, n_pad = ops.topk_geometry(Q, N, w,
                                                      max(bins, K))
        want = None
        for s in shifts:
            t0 = time.perf_counter()
            fn = _race_program(bins, s)
            out = jax.block_until_ready(fn(q, x))
            compile_s = time.perf_counter() - t0
            secs = []
            for _ in range(args.reps):
                t = time.perf_counter()
                out = jax.block_until_ready(fn(q, x))
                secs.append(time.perf_counter() - t)
            got = [np.asarray(v) for v in out[:3]]
            if want is None:
                want = got
            row = {"d": d, "shift": s,
                   "lanes": (tuning.race_lanes(bins, s) + (1 << s) if s
                             else bins),
                   "grid": [q_pad // bq, n_pad // bn], "bq": bq, "bn": bn,
                   "sub": sub, "median_s": statistics.median(secs),
                   "secs": secs, "first_call_s": compile_s,
                   "fine_tiles_run": int(out[3]),
                   "same_radius": all((a == b).all()
                                      for a, b in zip(got, want)),
                   "r_star_median": float(np.median(got[0]))}
            rows.append(row)
            print(json.dumps(row), flush=True)
        del x

    one = {r["d"]: r["median_s"] for r in rows if r["shift"] == 0}
    summary = {
        "device_kind": dev[0].device_kind, "n": N, "q": Q, "k": K,
        "all_same_radius": all(r["same_radius"] for r in rows),
        "best": {d: min((r for r in rows if r["d"] == d),
                        key=lambda r: r["median_s"])["shift"] for d in one},
        "speedup_vs_one_level": {
            f"{r['d']}/s{r['shift']}": one[r["d"]] / r["median_s"]
            for r in rows if r["shift"]},
        "race_shift": {d: tuning.race_shift(d + 1) for d in one}}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "race_levels.json"), "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
