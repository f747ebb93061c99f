"""Benchmark harness: one function per paper table (+ kernel/roofline).

  PYTHONPATH=src python -m benchmarks.run [--only table1,kernel]

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list, e.g. table1,kernel")
    args, _ = ap.parse_known_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from . import tables
    from .kernel_bench import kernel_bench, roofline_rows
    from .serve_bench import serve_bench

    suite = {
        "table1": tables.table1_ppl,
        "table2": tables.table2_zeroshot,
        "table3": tables.table3_ap,
        "table4": tables.table4_or,
        "table5": tables.table5_outlier_standard,
        "table6": tables.table6_or_split,
        "table7": tables.table7_bit_pairs,
        "table12": tables.table12_heuristic_search,
        "table13": tables.table13_calibration,
        "kernel": kernel_bench,
        "roofline": roofline_rows,
        "serve": serve_bench,
    }
    only = [s for s in args.only.split(",") if s]
    failures = 0
    for name, fn in suite.items():
        if only and name not in only:
            continue
        try:
            fn()
        except Exception as e:
            failures += 1
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
