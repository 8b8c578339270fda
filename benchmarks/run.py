"""Benchmark harness: one module per paper figure/claim.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig11_left,...]

Prints ``name,us_per_call,derived`` CSV rows (plus '#' commentary lines)
and persists every suite's rows to ``benchmarks/results/BENCH_<suite>.json``
(host info + git rev + delta vs the previous committed run).

  bench_spam      -> Fig. 11 left   (FL vs FL+DP accuracy, epsilon)
  bench_async     -> Fig. 11 center (sync vs async vs over-participation)
  bench_scaling   -> Fig. 11 right  (duration vs concurrent clients)
  bench_secureagg -> §3.1.2 VG cost model (O(n^2) -> O(n*g))
  bench_kernels   -> kernel microbenchmarks
  bench_fleet     -> fleet-scale control plane (10^6 devices, wave agg)
  bench_compression -> LoRA + top-k sub-1% rounds under secure agg
  bench_trace     -> flight-recorder overhead (<2%) + bit-identity gate
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_async, bench_cohort, bench_compression,
                        bench_fleet, bench_kernels, bench_scaling,
                        bench_secureagg, bench_spam, bench_trace)
from benchmarks.common import write_bench_json
from repro import compile_cache

SUITES = [
    ("fig11_left", bench_spam),
    ("fig11_center", bench_async),
    ("fig11_right", bench_scaling),
    ("secureagg_vg", bench_secureagg),
    ("kernels", bench_kernels),
    ("cohort_engine", bench_cohort),
    ("fleet", bench_fleet),
    ("compression", bench_compression),
    ("trace", bench_trace),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    compile_cache.configure()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for name, mod in SUITES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = mod.main(quick=args.quick)
            for r in rows:
                print(",".join(str(x) for x in r))
            print(f"# wrote {write_bench_json(name, rows, args.quick)}")
            print(f"# suite {name} done in {time.time() - t0:.1f}s")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# suite {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
