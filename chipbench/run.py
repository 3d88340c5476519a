#!/usr/bin/env python3
"""The on-chip benchmark: one cell of BENCHMARK.json in one process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment on the device from ``--seed`` and warms it up
(set-up), drives the cell's closed loop for ``--seconds``, compares what the
timed path produced with the plain reference, and prints one JSON object as
the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared beside its limit (also the last lines of
standard error). Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result: it never falls back to the CPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))    # the system under test


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import runner, spec
    try:
        import repro  # noqa: F401  (the system under test, from src/)
        cell = spec.load_cell(args.workload, root=ROOT)
        devices = runner.tpu_devices(cell.chips)
    except (ImportError, OSError, KeyError, ValueError, runner.NoChip) as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    cache = runner.enable_compile_cache(ROOT)
    print(f"chipbench: {cell.name} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}, compile cache {cache}",
          file=sys.stderr, flush=True)
    result = runner.run_cell(runner.make_ctx(cell, args.seed, devices),
                             args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
