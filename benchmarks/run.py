"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
``--fast`` (or REPRO_FAST=1) runs reduced sizes for CI.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig45]
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import (table1_kernel_svm, table2_wordpairs, fig45_cws_mse,
                        fig6_tstar_only, fig78_linear_svm, bench_cws_kernel,
                        roofline)
from repro.launch.compile_cache import setup_compile_cache

SUITES = {
    "table1": table1_kernel_svm.run,
    "table2": table2_wordpairs.run,
    "fig45": fig45_cws_mse.run,
    "fig6": fig6_tstar_only.run,
    "fig78": fig78_linear_svm.run,
    "cws_kernel": bench_cws_kernel.run,
    "roofline": roofline.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    default=bool(os.environ.get("REPRO_FAST")))
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    setup_compile_cache()

    failures = []
    for name, fn in SUITES.items():
        if args.only and args.only != name:
            continue
        print(f"# === {name} ===", flush=True)
        try:
            fn(fast=args.fast)
        except Exception as e:
            failures.append((name, e))
            traceback.print_exc()
    if failures:
        print(f"# {len(failures)} benchmark suites FAILED:"
              f" {[n for n, _ in failures]}")
        raise SystemExit(1)
    print("# all benchmark suites passed")


if __name__ == "__main__":
    main()
