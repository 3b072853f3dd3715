# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark aggregator.

Reproduces the paper's figures (3, 4/8/9, 5, 6/7, §4.2) via the calibrated
discrete-event farm plus a real shard_map farm run, then appends kernel
micro-benchmarks and the roofline rows derived from the multi-pod dry-run
artifacts (if present).

Run:  PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import sys
import traceback

from benchmarks.common import emit


def main() -> None:
    from repro import compile_cache

    compile_cache.enable()
    modules = [
        "benchmarks.accumulator_scaling",
        "benchmarks.accumulator_frequency",
        "benchmarks.successive_approximation",
        "benchmarks.separate_state_speedup",
        "benchmarks.partitioned_scaling",
        "benchmarks.shardmap_farm",
        "benchmarks.elastic_runtime",
        "benchmarks.keyed_throughput",
        "benchmarks.keyed_migration",
        "benchmarks.keyed_fused",
        "benchmarks.slo_loop",
        "benchmarks.dist_plane",
        "benchmarks.chaos_recovery",
        "benchmarks.kernel_bench",
        "benchmarks.roofline",
    ]
    print("name,us_per_call,derived")
    failures = []
    for modname in modules:
        try:
            mod = __import__(modname, fromlist=["run"])
            emit(mod.run())
        except Exception:  # pragma: no cover
            failures.append(modname)
            print(f"{modname}/ERROR,0.0,{traceback.format_exc(limit=1)!r}")
    if failures:
        print(f"# FAILED MODULES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
