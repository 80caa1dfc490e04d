"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Scaled-down defaults keep a
full run under ~10 minutes on the CPU container; pass --full for the
paper-scale protocol.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig1,table2]
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()  # before the first compile
    from benchmarks.bench_ablation import bench_table2
    from benchmarks.bench_cacheopt import bench_table3
    from benchmarks.bench_compute import bench_compute
    from benchmarks.bench_eviction import bench_eviction
    from benchmarks.bench_query import bench_batch, bench_table1
    from benchmarks.bench_storage import bench_loading, bench_redundancy

    suites = {
        "fig1": lambda: bench_compute(
            n=2000 if not args.full else 20000),
        "fig3": lambda: bench_redundancy(
            n_queries=6 if not args.full else 30) + bench_loading(),
        "table1": lambda: bench_table1(
            n_queries=8 if not args.full else 50),
        "table2": lambda: bench_table2(
            n_queries=5 if not args.full else 30,
            ratios=(0.2, 0.9, 1.0) if not args.full
            else (0.2, 0.9, 0.96, 0.98, 1.0)),
        "table3": lambda: bench_table3(
            n_probe=4 if not args.full else 10),
        # beyond-paper: eviction-policy ablation (paper §4.1 pluggable)
        "eviction": lambda: bench_eviction(
            n_rounds=6 if not args.full else 12),
        # beyond-paper: cross-query fetch amortization (DESIGN.md §5)
        "batch": lambda: bench_batch(
            batch_sizes=(1, 4, 16) if not args.full
            else (1, 2, 4, 8, 16, 32),
            n_queries=16 if not args.full else 32),
    }
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # keep the harness going
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
