"""Benchmark driver: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).

  fig3   -- scheduling-solver quality (rel. error + iterations)
  tab2   -- scheduled count + WEMD per algorithm
  fig4-5 -- FL accuracy, balanced + imbalanced total dataset
  fig8   -- scheduled count vs Dirichlet alpha (full V=64 + channel)
  fig9   -- G / sigma indicator dynamics
  eq9    -- Lambert-W bandwidth vs bisection oracle
  kernel -- Pallas kernels (interpret-mode correctness path)
  multicell -- multi-cell round engine throughput (rounds/sec vs C,
            fused vs pre-fusion round core; writes BENCH_multicell.json)
  roofline -- aggregates the dry-run artifacts (the Roofline table)

Usage: PYTHONPATH=src python -m benchmarks.run [--only fig3,tab2]
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache


BENCHES = [
    ("fig3", "benchmarks.bench_scheduling"),
    ("eq9", "benchmarks.bench_bandwidth"),
    ("fig8", "benchmarks.bench_fl_dirichlet"),
    ("kernel", "benchmarks.bench_kernels"),
    ("multicell", "benchmarks.bench_multicell"),
    ("roofline", "benchmarks.bench_roofline"),
    ("tab2", "benchmarks.bench_wemd_table"),
    ("fig9", "benchmarks.bench_gsigma"),
    ("fig4-5", "benchmarks.bench_fl_accuracy"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated bench tags to run")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(t for t in args.only.split(",") if t)

    print("name,us_per_call,derived")
    failures = 0
    for tag, modname in BENCHES:
        if only and tag not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["run"])
            for line in mod.run():
                print(line, flush=True)
            print(f"# {tag} done in {time.time() - t0:.0f}s", flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"# {tag} FAILED: {type(e).__name__}: {e}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
