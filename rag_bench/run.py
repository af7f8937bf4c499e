#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once on the chip it is started on.

    python3 rag_bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

In one process: build the cell's configuration (corpus and index from
this checkout's snapshot, generator weights from the seed), warm every
program the window runs, drive `RagSession.submit`/`step` with the
cell's traffic for `--seconds`, wait for what is in flight, check what
the window produced against the plain references, and print one JSON
line last. With `--trace 0` its metrics are the cell's end-to-end
metrics, measured with tracing off; with `--trace 1` they are the cell's
per-layer metrics, read from the program's spans and a device trace of
part of the window.

It exits nonzero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when anything fails before the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# a new trace, a backend compile, or a program loaded from the compile
# cache: none may happen inside the window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int, platforms=("tpu",)):
    """The devices the cell runs on: `n` of them, of an accelerator
    platform. A run anywhere else would measure nothing the benchmark
    is about, so it stops here."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in platforms:
        raise SystemExit(f"rag_bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r})")
    if len(devs) < n:
        raise SystemExit(f"rag_bench: the cell needs {n} chips, JAX found "
                         f"{len(devs)}")
    return devs[:n]


def reader(name: str):
    """The per-layer metric's reader, `metrics/<name>.py`."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "rag_bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, key: str, cell: str) -> list:
    return [m for m in bench[key]
            if cell in m.get("workloads", [cell])]


def finite(v):
    return v if v is not None and math.isfinite(v) else None


def run(args, *, platforms=("tpu",), bench_file=CHECKOUT / "BENCHMARK.json",
        cache=BENCH / "cache",
        log=lambda *a: print(*a, file=sys.stderr)) -> dict:
    """One run of one cell; returns the result object. `cache` holds the
    index snapshot, the compile cache and the trace."""
    from rag_bench import harness
    cell = harness.load_cell(args.workload, bench_file)
    bench = harness.load_json(bench_file)
    devs = require_chips(cell.chips, platforms)
    harness.configure_jax(cache / "jax")
    trace_dir = cache / "trace"
    import jax
    from rag_bench import check

    conf, mix = cell.config, cell.mix
    stack = harness.build(conf, mix, args.seed, trace=bool(args.trace),
                          state_root=cache / "index")
    harness.warm(stack, conf, mix, args.seed)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **_: compiles.append(ev) if ev in COMPILE_EVENTS
        else None)
    stop = harness.record_outputs(stack)
    span = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        lead = min(2.0, args.seconds / 4)
        span = (lead, min(3.0, args.seconds - lead))
    setup_s = time.perf_counter() - T_START
    n_compiles = len(compiles)
    log_w = harness.drive(stack, mix, cell.params.get("rate_rps"),
                          args.seconds, args.seed,
                          trace_dir=trace_dir if args.trace else None,
                          trace_span=span or (0.0, 0.0))
    n_compiles = len(compiles) - n_compiles
    stop()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    e2e = harness.end_to_end(log_w)
    out = harness.outcome(log_w)
    weight_seed = harness.sub_seed(args.seed, "weights")
    ev = check.gather(stack, log_w, args.seed, weight_seed,
                      int(cell.params["check_requests"]))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        metrics, breakdown, dev_extra = traced_metrics(
            bench, cell, conf, stack, log_w, ev, trace_dir)
        device.update(dev_extra)
    else:
        metrics = {}
        for m in cell_metrics(bench, "end_to_end", cell.name):
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if finite(v) is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = None
    setup = dict(stack.timings, total_s=setup_s)
    harness.free(stack)
    t0 = time.perf_counter()
    nums, _ = check.numbers(ev, conf)
    check_s = time.perf_counter() - t0
    ok, shown = check.verdict(nums, cell.params["limits"])
    result.update(correct=ok, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["setup"] = setup
    result["load"] = {k: out[k] for k in out if k.startswith("generator")}
    result["load"].update(compiles_in_window=n_compiles,
                          check_s=check_s,
                          checked_requests=len(ev.sequences),
                          checked_tokens=check.served_tokens(ev),
                          retrieval_calls=len(ev.retrievals),
                          select_calls=len(ev.selects))
    result["checks"] = {k: {"value": finite(v["value"]),
                            "limit": v["limit"]} for k, v in shown.items()}
    for k, v in shown.items():
        log(f"[check] {k} = {v['value']!r} (limit {v['limit']!r})")
    return result


def traced_metrics(bench, cell, conf, stack, log_w, ev, trace_dir):
    """The cell's per-layer metrics from the spans, the device trace and
    the counted work of the traced span."""
    from rag_bench import counts, harness, observe, trace_reduce
    import jax
    peaks = harness.load_json(BENCH / "peaks.json")
    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"rag_bench: no peaks for device kind {kind!r}")
    peaks = peaks[kind]
    records = stack.sink.records()
    lo, hi = log_w.traced
    model = counts.Model.from_config(conf)
    work = observe.engine_work(records, (lo, hi), model)
    work.update(observe.kernel_work(stack.retrievals, stack.selects,
                                    (lo, hi), ev.index, ev.window_lens))
    pd = trace_reduce.load(str(trace_dir))
    red = trace_reduce.reduce_profile(pd)
    if not red.programs:
        for line in trace_reduce.describe(pd):
            print(f"[trace] {line}", file=sys.stderr)
    expected = {role: (mod, work[role].calls)
                for role, mod in observe.MODULES.items()}
    roles = trace_reduce.assign_roles(red, expected)
    for role, (mod, calls) in expected.items():
        if role not in roles.values():
            print(f"[trace] {role}: no {mod} program ran exactly its {calls} "
                  "span-counted times; its metrics are left out",
                  file=sys.stderr)
    obs = observe.Observed(records, (log_w.start, log_w.end), (lo, hi), red,
                           roles, work, peaks, {"window_s": hi - lo})
    metrics = {}
    for m in cell_metrics(bench, "per_layer", cell.name):
        v = reader(m["name"])(obs)
        if finite(v) is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    names = {k: f"{r}({k[0]})" for k, r in roles.items()}
    breakdown = {"device_ops": trace_reduce.top_ops(red, names),
                 "idle_gaps": trace_reduce.idle_gaps(red)}
    runs = {r: [trace_reduce.role_runs(red, roles, r), work[r].calls]
            for r in observe.MODULES}
    print(f"[trace] program runs in trace vs spans: {runs}",
          file=sys.stderr)
    for p in red.programs[:12]:
        print(f"[trace] {p.module}({p.program_id}) runs={p.runs} "
              f"device_s={p.time_s!r} role={roles.get((p.module, p.program_id))}",
              file=sys.stderr)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return metrics, breakdown, {"busy_s": red.busy_s, "window_s": hi - lo}


def main(argv=None) -> None:
    args = parse_args(argv)
    result = run(args)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
