"""Benchmark orchestrator: one experiment per paper table/figure.

Prints ``name,us_per_call,derived``-style CSV lines per experiment and
writes JSON artifacts under results/bench/.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    import os

    sys.path.insert(0, os.path.dirname(__file__))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

    from benchmarks import (
        bench_calibration,
        bench_kernels,
        bench_multistream,
        bench_network,
        bench_optimal_gap,
        bench_policy_planner,
        bench_reliability,
        bench_resolution,
        bench_threshold_sweep,
        bench_tiers,
    )
    from benchmarks.common import build_stack
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    build_stack()  # train/cache the two-tier stack once
    results = {}
    for mod in (bench_calibration, bench_reliability, bench_threshold_sweep,
                bench_resolution, bench_tiers, bench_kernels,
                bench_network, bench_optimal_gap, bench_policy_planner,
                bench_multistream):
        name = mod.__name__.split(".")[-1]
        print(f"=== {name} ===", flush=True)
        t = time.time()
        results[name] = mod.run()
        print(f"=== {name} done in {time.time()-t:.1f}s ===", flush=True)
    print(f"all benchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
