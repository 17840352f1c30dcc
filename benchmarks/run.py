"""Benchmark harness: one module per paper table/figure + kernel micro-
benchmarks. Prints ``name,us_per_call,derived`` CSV."""

from __future__ import annotations

import importlib
import sys
import traceback

MODULES = [
    "benchmarks.fig3_sparsity_stats",
    "benchmarks.fig10_accuracy",
    "benchmarks.fig11_speedup",
    "benchmarks.fig12_breakdown",
    "benchmarks.fig13_op_breakdown",
    "benchmarks.tab2_comparison",
    "benchmarks.tab3_exec_time",
    "benchmarks.kernel_bench",
]


def main() -> None:
    print("name,us_per_call,derived")
    failures = []
    for modname in MODULES:
        try:
            mod = importlib.import_module(modname)
            mod.run()
        except Exception as e:  # keep the harness going, report at the end
            failures.append((modname, e))
            traceback.print_exc()
    if failures:
        print(f"FAILED modules: {[m for m, _ in failures]}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
