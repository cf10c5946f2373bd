"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig4]

Output: CSV lines ``name,us_per_call,derived``.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from . import (bench_alpha_ablation, bench_build, bench_concurrent,
               bench_io_cost, bench_merge_recall, bench_merge_vs_rebuild,
               bench_recall_stability, bench_throughput, bench_update_path)
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("fig1_fig2_recall_stability", bench_recall_stability),
    ("fig3_alpha_ablation", bench_alpha_ablation),
    ("fig4_merge_recall", bench_merge_recall),
    ("tab1_build_time", bench_build),
    ("tab2_merge_vs_rebuild", bench_merge_vs_rebuild),
    ("fig5_fig6_concurrent", bench_concurrent),
    ("fig7_throughput_scaling", bench_throughput),
    ("sec6_io_cost", bench_io_cost),
    ("sec5_update_path", bench_update_path),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name, mod in MODULES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod.main(quick=args.quick)
            print(f"# {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr, flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
