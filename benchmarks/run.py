"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` runs a reduced subset
(used by CI-style checks); default runs everything.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

MODULES = [
    "benchmarks.bench_table2_costmodel",
    "benchmarks.bench_fig5_fig6_stage_costs",
    "benchmarks.bench_fig4_multistream",
    "benchmarks.bench_fig7_generation_stall",
    "benchmarks.bench_kernels",
    "benchmarks.bench_engine_throughput",
    "benchmarks.bench_prefill_ttft",
    "benchmarks.bench_serving_slo",
    "benchmarks.bench_cache",
    "benchmarks.bench_fault_recovery",
    "benchmarks.bench_fig13_breakdown",
    "benchmarks.bench_fig14_ablation",
    "benchmarks.bench_autotuner",
    "benchmarks.bench_fig11_node_ratio",
    "benchmarks.bench_fig12_method_vs_slo",
    "benchmarks.bench_fig10_goodput",
]
QUICK = MODULES[:11]  # original quick set + engine/serving/cache/faults


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filter on module names")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    mods = QUICK if args.quick else MODULES
    if args.only:
        keys = args.only.split(",")
        mods = [m for m in MODULES if any(k in m for k in keys)]
    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        t0 = time.time()
        try:
            mod = importlib.import_module(name)
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}", flush=True)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
