#!/usr/bin/env python3
"""Readings of a cell's control on the chip: the plain reference put in the
program's place with one guarantee broken (see the system's ``control``),
compared by the cell's own numbers over as many answers as a run of
``--seconds`` compares. Every seed has to come out not correct.

    python3 chipbench/control.py --workload <cell> --batches <b> --seeds <s> ...

One process reads every seed. Prints one JSON line per seed and exits 1 if
any seed's control came out correct. The benchmark's own runs never run
this.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", type=int, required=True,
                    help="batches a run compares")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from harness import runner, spec
    cell = spec.load_cell(args.workload, root=ROOT)
    devices = runner.tpu_devices(cell.chips)
    runner.enable_compile_cache(ROOT)
    system = spec.load_system(cell.config["system"])
    passed = 0
    for seed in args.seeds:
        checks = system.control(runner.make_ctx(cell, seed, devices),
                                args.batches)
        correct = all(v <= lim for v, lim in checks.values())
        passed += correct
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_correct": correct,
                          "checks": {n: {"value": v, "limit": lim}
                                     for n, (v, lim) in checks.items()}}),
              flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
