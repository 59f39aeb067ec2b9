"""Benchmark harness: one bench per paper table/figure + the roofline table.

  PYTHONPATH=src python -m benchmarks.run [--only table4]
Prints one CSV-ish line per result row.
"""
from __future__ import annotations

import argparse
import sys

from benchmarks import (
    bench_fig6_layer_sweep,
    bench_kernels,
    bench_model_error,
    bench_roofline_table,
    bench_table3_resources,
    bench_table4_vgg16,
)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "table3": bench_table3_resources.run,
    "table4": bench_table4_vgg16.run,
    "fig6": bench_fig6_layer_sweep.run,
    "model_error": bench_model_error.run,
    "kernels": bench_kernels.run,
    "roofline": bench_roofline_table.run,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    args = ap.parse_args()
    enable_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    failed = False
    for name in names:
        print(f"\n== {name} ==")
        try:
            for row in BENCHES[name]():
                print(",".join(f"{k}={v}" for k, v in row.items()))
        except Exception as e:  # noqa: BLE001
            failed = True
            print(f"BENCH FAIL {name}: {type(e).__name__}: {e}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
