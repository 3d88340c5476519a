#!/usr/bin/env python3
"""On a TPU: the knnlm.decode16 cell over many seeds in one process, to
set and confirm the limits of its check.

    python3 experiments/knnlm_sweep.py --seeds S1 S2 ... [--seconds 10]
        [--out DIR] [--workload knnlm.decode16]

Each seed is one whole run of the cell (``chipbench/harness/runner``):
set-up from the seed, the closed-loop window, the staged check against the
float32 reference. Compiled programs are shared across seeds (one process,
the same shapes), so a seed costs its set-up, window and check only. Prints
one JSON line per seed (``search_qps``, ``setup_s``, the fullest device's
memory, every check beside its limit, ``correct``) and a summary last: each
check's largest value over the seeds. Also written to
``<out>/knnlm_sweep.jsonl``. Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "chipbench"), os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="knnlm.decode16")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from harness import runner, spec
    cell = spec.load_cell(args.workload, root=ROOT)
    try:
        devices = runner.tpu_devices(cell.chips)
    except runner.NoChip as e:
        print(f"knnlm_sweep: {e}", file=sys.stderr)
        return 2
    runner.enable_compile_cache(ROOT)
    out = None
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        out = open(os.path.join(args.out, "knnlm_sweep.jsonl"), "w")
    worst = {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = runner.run_cell(runner.make_ctx(cell, seed, devices),
                              args.seconds, False, t0)
        line = {"seed": seed, "correct": res["correct"],
                "search_qps": res["metrics"]["search_qps"]["value"],
                "setup_s": res["metrics"]["setup_s"]["value"],
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "run_s": time.perf_counter() - t0,
                "checks": res["checks"]}
        for n, c in res["checks"].items():
            worst[n] = max(worst.get(n, float("-inf")), c["value"])
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    summary = {"seeds": len(args.seeds), "worst": worst}
    print(json.dumps(summary), flush=True)
    if out:
        out.write(json.dumps(summary) + "\n")
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
