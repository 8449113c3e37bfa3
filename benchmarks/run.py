"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (paper-artifact mapping in
DESIGN.md §6). ``--quick`` skips the slowest suites.
"""
import argparse
import sys
import traceback

SUITES = [
    ("table4_subshard_order", "benchmarks.bench_subshard_order"),
    ("fig7_partitioning", "benchmarks.bench_partitioning"),
    ("fig8_spu_dpu", "benchmarks.bench_spu_dpu"),
    ("fig9_memory", "benchmarks.bench_memory"),
    ("fig10_parallelism", "benchmarks.bench_parallelism"),
    ("fig11_scalability", "benchmarks.bench_scalability"),
    ("fig12_algorithms", "benchmarks.bench_algorithms"),
    ("tables56_fig6_systems", "benchmarks.bench_pagerank_systems"),
    ("serving", "benchmarks.bench_serving"),
    ("lm_step", "benchmarks.bench_lm_step"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()
    skip_slow = {"fig10_parallelism"} if args.quick else set()
    print("suite,name,us_per_call,derived")
    failures = []
    for suite, module in SUITES:
        if suite in skip_slow:
            continue
        if args.only and args.only not in suite:
            continue
        try:
            mod = __import__(module, fromlist=["run"])
            for line in mod.run():
                print(f"{suite},{line}", flush=True)
        except Exception as e:
            failures.append((suite, repr(e)))
            traceback.print_exc()
    if failures:
        print(f"FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
