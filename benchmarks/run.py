"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract) and writes
one machine-readable ``BENCH_<suite>.json`` per suite next to the CSV
(schema: :mod:`benchmarks.artifacts`; regression gate:
:mod:`benchmarks.gate`).  The roofline table (assignment deliverable g) is
emitted at the end when dry-run artifacts exist under experiments/dryrun/.
"""
from __future__ import annotations

import os
import sys
import traceback


def main() -> None:
    from . import (
        bench_accuracy,
        bench_densification,
        bench_hubs,
        bench_interarrival,
        bench_kernel,
        bench_throughput,
    )
    from repro.launch.compile_cache import use_compile_cache

    from .artifacts import write_bench_json

    use_compile_cache()
    suites = [
        ("densification", bench_densification.run),
        ("hubs", bench_hubs.run),
        ("interarrival", bench_interarrival.run),
        ("accuracy", bench_accuracy.run),
        ("throughput", bench_throughput.run),
        ("streaming", bench_throughput.run_streaming),
        ("kernel", bench_kernel.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            rows = list(fn())
            for n, us, derived in rows:
                print(f"{n},{us:.1f},{derived}")
            write_bench_json(f"BENCH_{name}.json", rows)
        except Exception:
            failures += 1
            print(f"{name},NaN,ERROR", file=sys.stdout)
            traceback.print_exc(file=sys.stderr)

    # roofline summary (if the dry-run has been executed)
    if os.path.isdir("experiments/dryrun/pod"):
        from .roofline import format_table, roofline_table
        print("\n# Roofline (single-pod, per chip) — see EXPERIMENTS.md")
        print(format_table(roofline_table()))
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
