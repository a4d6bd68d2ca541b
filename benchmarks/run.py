"""Benchmark harness entry point: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick (CPU budget)
    PYTHONPATH=src python -m benchmarks.run --full
    PYTHONPATH=src python -m benchmarks.run --only fig1,fig8
"""

from __future__ import annotations

import argparse
import os
import time
import traceback

from benchmarks import (bench_fig1_throughput, bench_fig5_curves,
                        bench_fig8_routing_ops, bench_table1_pruning,
                        bench_table2_resources, bench_traffic,
                        common as bc)

BENCHES = {
    "fig1": ("Fig.1 throughput orig/pruned/optimized",
             bench_fig1_throughput.run),
    "table1": ("Table I LAKP vs KP error", bench_table1_pruning.run),
    "fig5": ("Fig.5 compression curves", bench_fig5_curves.run),
    "fig8": ("Fig.8 routing op latency", bench_fig8_routing_ops.run),
    "table2": ("Tables II/III resources", bench_table2_resources.run),
    "traffic": ("Traffic replay: autoscaled vs static pool",
                bench_traffic.run),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig1,fig8")
    ap.add_argument("--json-dir", default=None,
                    help="write one machine-readable BENCH_<key>.json "
                         "perf-trajectory record per bench to this dir")
    args = ap.parse_args()
    bc.enable_compile_cache()
    only = set(args.only.split(",")) if args.only else set(BENCHES)

    failures = []
    t_start = time.time()
    for key, (title, fn) in BENCHES.items():
        if key not in only:
            continue
        print(f"\n##### [{key}] {title} " + "#" * 20)
        t0 = time.time()
        try:
            results = fn(quick=not args.full)
            print(f"[{key}] done in {time.time() - t0:.1f}s")
            if args.json_dir:
                bc.write_bench_json(
                    os.path.join(args.json_dir, f"BENCH_{key}.json"),
                    key, results, mode="full" if args.full else "quick")
        except Exception as e:  # noqa: BLE001 — report all benches
            failures.append((key, repr(e)))
            traceback.print_exc()
    print(f"\nTotal: {time.time() - t_start:.1f}s")
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("All benchmarks completed.")


if __name__ == "__main__":
    main()
