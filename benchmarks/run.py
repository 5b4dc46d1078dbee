"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig6a fig8 # subset by tag

Prints ``name,us_per_call,derived`` CSV rows.
"""

from __future__ import annotations

import sys
import traceback

BENCHES = [
    ("table1", "benchmarks.bench_restart_breakdown"),
    ("fig6a", "benchmarks.bench_reconfig"),
    ("fig6b", "benchmarks.bench_storage"),
    ("fig6c", "benchmarks.bench_breakdown"),
    ("fig6d", "benchmarks.bench_interference"),
    ("fig7_8", "benchmarks.bench_volatility"),
    ("fig9", "benchmarks.bench_parity"),
    ("fig10", "benchmarks.bench_simvalidate"),
    ("fig11", "benchmarks.bench_scale"),
    ("plan", "benchmarks.bench_plan"),
    ("movefrac", "benchmarks.bench_move_fraction"),
    ("roofline", "benchmarks.bench_roofline"),
    ("dataplane", "benchmarks.bench_dataplane"),
    ("delta", "benchmarks.bench_delta"),
    ("goodput", "benchmarks.bench_goodput"),
    ("faults", "benchmarks.bench_faults"),
    ("serve", "benchmarks.bench_serve_goodput"),
    ("fleet", "benchmarks.bench_fleet"),
]


def main() -> None:
    args = sys.argv[1:]
    if "--help" in args or "-h" in args:
        print(__doc__.strip())
        print("\nTags:")
        for tag, module in BENCHES:
            print(f"  {tag:10s} {module}")
        return
    tags = set(args)
    unknown = tags - {tag for tag, _ in BENCHES}
    if unknown:
        sys.exit(f"unknown tags {sorted(unknown)}; run with --help for the list")
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for tag, module in BENCHES:
        if tags and tag not in tags:
            continue
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main()
        except Exception as e:  # keep the suite going
            failures.append((tag, e))
            print(f"{tag}/ERROR,0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
