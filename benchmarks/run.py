"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,table3]

Prints ``name,us_per_call,derived`` CSV rows (stdout), one per measurement.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

from benchmarks.common import emit
from repro.launch.compile_cache import enable_compile_cache

ALL = ["fig1", "fig2", "fig3", "table1", "table3", "table6", "kernels",
       "outofcore", "trace", "serve", "slo", "svr", "oneclass", "eq_block",
       "dist"]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shapes / few iterations: CI smoke, not timing")
    args = ap.parse_args()
    names = [n for n in args.only.split(",") if n] or ALL
    failures = []
    print("name,us_per_call,derived")
    for name in names:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.perf_counter()
        try:
            kw = {}
            if args.dry_run and "dry_run" in inspect.signature(mod.run).parameters:
                kw["dry_run"] = True
            rows = mod.run(**kw)
            emit(rows)
            print(f"# bench_{name}: ok in {time.perf_counter()-t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
