"""Benchmark harness entry point — one bench per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; the kernels bench additionally
writes BENCH_kernels.json (the perf-trajectory artifact CI records).

  PYTHONPATH=src python -m benchmarks.run [--budget small|full] [--only X]
"""
from __future__ import annotations

import argparse
import inspect
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

BENCHES = [
    ("table3_mnist", "benchmarks.bench_table3_mnist"),
    ("table5_xray", "benchmarks.bench_table5_xray"),
    ("fig7_crop", "benchmarks.bench_fig7_crop"),
    ("fig8_hyperparams", "benchmarks.bench_fig8_hyperparams"),
    ("fig10_dynamic_alpha", "benchmarks.bench_fig10_dynamic_alpha"),
    ("communication", "benchmarks.bench_communication"),
    # order no longer matters for the JSON artifact: every bench merges
    # its rows by section key through common.merge_rows (replace
    # same-name rows, preserve the rest) instead of rewriting wholesale
    ("kernels", "benchmarks.bench_kernels"),
    ("scenarios", "benchmarks.bench_scenarios"),
    ("serve", "benchmarks.bench_serve"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="small", choices=["small", "full"])
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    failed = []
    for name, module in BENCHES:
        if args.only and args.only not in name:
            continue
        print(f"# ==== {name} ====", flush=True)
        try:
            mod = __import__(module, fromlist=["main"])
            if "budget" in inspect.signature(mod.main).parameters:
                mod.main(budget=args.budget)
            else:
                mod.main()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print("# FAILED:", ",".join(failed))
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
