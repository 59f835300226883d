# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per thesis table/figure:

  bench_nero       Ch.3  Figs 3-6/3-7   NERO window autotune + scaling
  bench_precision  Ch.4  Fig 4-4/T4.2   number-system accuracy sweeps
  bench_napel      Ch.5  Figs 5-4/5/7   perf/energy prediction + speedup
  bench_leaper     Ch.6  Fig 6-4/T6.6   few-shot cross-platform transfer
  bench_sibyl      Ch.7  Figs 7-10..19  RL data placement vs baselines
  bench_roofline   —     §Dry-run/§Roofline cell table
  bench_serve      —     serve layer: fused vs eager decode, host
                         bookkeeping, continuous-batching throughput
  bench_traffic    —     open-loop trace replay through the async front
                         end; persists BENCH_traffic.json trajectory

Run: PYTHONPATH=src python -m benchmarks.run [--only nero,sibyl]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

SUITES = ("roofline", "nero", "precision", "napel", "leaper", "sibyl",
          "serve", "traffic")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SUITES))
    args, _ = ap.parse_known_args()
    picked = args.only.split(",") if args.only else list(SUITES)
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for suite in picked:
        mod_name = f"benchmarks.bench_{suite}"
        t0 = time.time()
        try:
            __import__(mod_name)
            mod = sys.modules[mod_name]
            for name, us, derived in mod.run():
                print(f"{name},{us:.2f},{derived}")
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{suite}.FAILED,0,error")
        print(f"{suite}.suite_wall,{(time.time() - t0) * 1e6:.0f},total",
              flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
