"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--mode quick|full] [--only X]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = [
    ("memory", "benchmarks.bench_memory"),            # Fig 6 / Table 1
    ("recall_qps", "benchmarks.bench_recall_qps"),    # Fig 7 / Fig 8
    ("power", "benchmarks.bench_power"),              # Fig 9 / §3.4.3
    ("update", "benchmarks.bench_update"),            # Fig 10
    ("centroids", "benchmarks.bench_centroids"),      # Fig 11
    ("scr", "benchmarks.bench_scr"),                  # Table 4 / Fig 12
    ("rag_e2e", "benchmarks.bench_rag_e2e"),          # Table 5
    ("battery", "benchmarks.bench_battery"),          # Table 6
    ("kernels", "benchmarks.bench_kernels"),          # kernels (extra)
    ("serving", "benchmarks.bench_serving"),          # wave vs continuous
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="quick", choices=["quick", "full"])
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, module in SUITES:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            mod.run(args.mode)
            print(f"suite.{name},{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            print(f"suite.{name},{(time.time()-t0)*1e6:.0f},"
                  f"FAILED:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
