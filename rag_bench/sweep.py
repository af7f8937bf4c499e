#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow over the window. One process builds and warms the cell
once, then offers each rate for `--seconds` (with a drain after each).

    python3 rag_bench/sweep.py --workload <cell> --seed <n> \
        --seconds 10 --rates 10 15 20 25 30

For each rate it prints one JSON line: requests due and done, the
backlog's growth (least-squares slope of requests in flight over the
window's second half, in requests per second), TTFT p50/p95 of the
window's first and second halves, ITL p95 and tokens per second. The
knee is recorded in the cell's file (`cells/<cell>.json`) and PERF.md;
the benchmark's runs never search for it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402


def backlog_slope(log) -> float:
    """Requests in flight over the second half of the window, as a
    least-squares slope (requests per second)."""
    mid = log.start + (log.end - log.start) / 2
    ts = np.linspace(mid, log.end, 64)
    fly = [sum(1 for r in log.reqs.values()
               if r.due <= t and (r.end is None or r.end > t)) for t in ts]
    return float(np.polyfit(ts - ts[0], fly, 1)[0])


def halves(log) -> dict:
    mid = log.start + (log.end - log.start) / 2
    out = {}
    for name, lo, hi in (("first", log.start, mid), ("second", mid, log.end)):
        ttft = [((r.tokens[0] if r.tokens and r.tokens[0] <= log.end
                  else log.end) - r.due)
                for r in log.reqs.values() if lo <= r.due < hi]
        if ttft:
            out[f"ttft_p50_ms_{name}"] = float(np.percentile(ttft, 50) * 1e3)
            out[f"ttft_p95_ms_{name}"] = float(np.percentile(ttft, 95) * 1e3)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from rag_bench import harness
    from rag_bench.run import require_chips
    cell = harness.load_cell(args.workload)
    require_chips(cell.chips)
    harness.configure_jax()
    stack = harness.build(cell.config, cell.mix, args.seed, trace=False)
    harness.warm(stack, cell.config, cell.mix, args.seed)
    print(json.dumps({"setup": stack.timings}), flush=True)
    for rate in args.rates:
        log = harness.drive(stack, cell.mix, rate, args.seconds, args.seed)
        row = {"rate_rps": rate, **harness.outcome(log),
               "backlog_slope_rps": backlog_slope(log),
               **harness.end_to_end(log), **halves(log)}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
