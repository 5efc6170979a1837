"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table2 fig3 ...]
    PYTHONPATH=src python -m benchmarks.run --check

Emits ``name,us_per_call,derived`` CSV rows (plus human tables) for:
  table2   — Table II  clustering rand index (TNN / DTCR / k-means)
  table34  — Tables III+IV  post-P&R leakage + area, 3 libraries
  fig2     — Fig. 2  computation latency + simulator mode comparison
  fig3     — Fig. 3  P&R runtime ASAP7 vs TNN7
  table5   — Table V  area/leakage forecasting + errors
  kernels  — Pallas kernel sweeps (beyond paper)
  train    — fused online-STDP training (columns + multi-layer network)
             vs legacy loops (BENCH_train.json)
  dse      — fault-isolation + journal overhead of the design sweep
  serve    — streaming clustering service req/s + latency (BENCH_serve.json)
  costmodel — device-calibrated cost model: predicted vs measured step time

``--check`` imports every registered benchmark and exits nonzero if any
fails to import, so the reproduction commands documented in README.md
cannot silently rot.  Modules are imported lazily either way: one broken
benchmark never takes down the others.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback

MODULES = {
    "table2": "benchmarks.table2_clustering",
    "table34": "benchmarks.table34_silicon",
    "fig2": "benchmarks.fig2_latency",
    "fig3": "benchmarks.fig3_runtime",
    "table5": "benchmarks.table5_forecast",
    "kernels": "benchmarks.kernels_bench",
    "train": "benchmarks.train_bench",
    "dse": "benchmarks.dse_bench",
    "serve": "benchmarks.serve_bench",
    "costmodel": "benchmarks.costmodel_bench",
}


def check(only=None) -> int:
    """Import the registered benchmarks; nonzero exit on any failure."""
    failed = []
    checked = 0
    for name, path in MODULES.items():
        if only and name not in only:
            continue
        checked += 1
        try:
            mod = importlib.import_module(path)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            continue
        if not callable(getattr(mod, "main", None)):
            print(f"{name}: {path} has no callable main()")
            failed.append(name)
    if failed:
        print(f"FAILED import check: {failed}")
        return 1
    print(f"all {checked} checked benchmarks import cleanly")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", choices=tuple(MODULES), default=None)
    ap.add_argument(
        "--check", action="store_true",
        help="only verify every benchmark imports; exit nonzero on failure",
    )
    args = ap.parse_args()
    if args.check:
        return check(args.only)
    failed = []
    for name, path in MODULES.items():
        if args.only and name not in args.only:
            continue
        print(f"\n===== {name} =====")
        try:
            importlib.import_module(path).main([])
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"\nFAILED benchmarks: {failed}")
        return 1
    print("\nall benchmarks complete")
    return 0


if __name__ == "__main__":
    sys.exit(main())
