"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig4] [--json PATH]

``--json PATH`` additionally writes the rows machine-readable (list of
{name, us_per_call, derived:{...}} objects) so the perf trajectory is
diffable across PRs; CI names these BENCH_<tag>.json.
"""
import argparse
import json
import sys
import traceback

from benchmarks import (bench_approx, bench_compounding, bench_energy_proxy,
                        bench_indexing, bench_mutate, bench_packing,
                        bench_serve, bench_shardfault,
                        bench_statistical_reduction, bench_tenant,
                        bench_throughput, bench_workloads)
from repro.launch import cache

BENCHES = [
    ("fig4", bench_throughput),
    ("fig5", bench_indexing),
    ("approx", bench_approx),
    ("fig6", bench_energy_proxy),
    ("table2", bench_workloads),
    ("fig8", bench_packing),
    ("fig11", bench_statistical_reduction),
    ("fig15", bench_compounding),
    ("serve", bench_serve),
    ("mutate", bench_mutate),
    ("tenant", bench_tenant),
    ("shardfault", bench_shardfault),
]


def _parse_row(line: str) -> dict:
    """'name,123.4,qps=10;speedup=2.0x' -> structured record. Lines that
    don't follow the row() shape are kept raw rather than failing the run."""
    try:
        name, us, derived = line.split(",", 2)
        fields = {}
        for part in filter(None, derived.split(";")):
            key, _, val = part.partition("=")
            try:
                fields[key] = float(val.rstrip("x"))
            except ValueError:
                fields[key] = val
        return {"name": name, "us_per_call": float(us), "derived": fields}
    except ValueError:
        return {"raw": line}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON list to PATH")
    args = ap.parse_args()

    rows = []

    def report(line: str) -> None:
        print(line, flush=True)
        if args.json:
            rows.append(_parse_row(line))

    selected = [(tag, mod) for tag, mod in BENCHES
                if not args.only or args.only in tag]
    if not selected:
        ap.error(f"--only {args.only!r} matches no benchmark; tags: "
                 f"{', '.join(tag for tag, _ in BENCHES)}")
    cache.enable_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    for tag, mod in selected:
        try:
            mod.run(report)
        except Exception:  # noqa: BLE001
            failed.append(tag)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"wrote {len(rows)} rows to {args.json}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
