"""Serving latency under a ragged request stream: wave vs continuous.

Workload: requests with ragged prompt lengths and ragged generation
budgets arriving as a Poisson process (rate auto-calibrated to ~80% of
the engine's measured decode capacity, so the queue is loaded but not
saturated on any host speed).

Baseline ("wave"): the legacy Engine surface — up to `slots` queued
requests form a fixed-shape wave (prompts left-padded to one bucket
length, exactly what the bucketed sLM path did) and the wave blocks until
its slowest member finishes; arrivals during a wave wait for the next one.

Continuous: the slot-paged ContinuousEngine — a queued prompt is admitted
into any slot the step after its occupant hits EOS, its prefill chunked
into the running decode loop, every request stops at its own budget.

Emits p50/p95 request latency (submit -> last token) for both, plus slot
utilisation for the continuous engine.

A second section exercises the post-PR-5 coverage of the paged path:
continuous-only rows for a sliding-window (ring-page) config, an int8-KV
config, an MoE config and a sampled (non-greedy, per-slot PRNG streams)
run — quick mode keeps one swa + one sampled row for the CI smoke.

A third section sweeps SHARED-PREFIX RATIO (0/50/90% of the prompt in
common across requests; quick mode keeps the 0/90 endpoints) and reports
p50 TTFT per share: the block-table pager maps cached prefix pages
instead of recomputing them, so TTFT must drop as the share rises —
`--prefix` runs just this sweep (the CI prefix smoke).

A fourth section measures GOODPUT UNDER CHAOS: 3 SlotScheduler replicas
wrapped in a seeded FaultPlan (replica crashes, slot stalls, slow steps —
serving/faults.py), per-request deadlines, and a
completed-within-deadline / submitted column beside the latency
percentiles. `python -m benchmarks.bench_serving --chaos` runs just that
section (the CI chaos smoke).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit

SLOTS = 4
PAD_LEN = 80            # wave bucket length (prompts padded up to this)
MAX_LEN = 128


def _workload(mode: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    n = 12 if mode == "quick" else 32
    plens = rng.integers(12, 72, size=n)
    gens = rng.integers(4, 20, size=n)
    prompts = [rng.integers(4, 500, p).astype(np.int32) for p in plens]
    return prompts, gens


def _pad(prompt: np.ndarray) -> np.ndarray:
    return np.concatenate(
        [np.zeros(PAD_LEN - len(prompt), np.int32), prompt])


def _arrivals(n: int, rate: float, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def _run_wave(eng, prompts, gens, arrivals):
    """FIFO waves of up to SLOTS requests; per-request latency = wave end
    (the wave blocks on its slowest member — the thing being measured)."""
    n = len(prompts)
    t0 = time.perf_counter()
    queue = []
    nxt = 0
    lat = {}
    while len(lat) < n:
        now = time.perf_counter() - t0
        while nxt < n and arrivals[nxt] <= now:
            queue.append(nxt)
            nxt += 1
        if not queue:
            time.sleep(max(arrivals[nxt] - now, 0.0) + 1e-4)
            continue
        wave, queue = queue[:SLOTS], queue[SLOTS:]
        eng.generate([_pad(prompts[i]) for i in wave],
                     max_new=int(max(gens[i] for i in wave)),
                     continuous=False)
        t_done = time.perf_counter() - t0
        for i in wave:
            lat[i] = t_done - arrivals[i]
    return np.array([lat[i] for i in range(n)])


def _run_continuous(ce, prompts, gens, arrivals, greedy=True):
    """Drive the open-loop workload and derive per-request latency and
    TTFT from the trace spans (queued -> first_token -> done) instead of
    ad-hoc timers: the bench reports exactly what the sink records, so a
    production JSONL export reproduces these numbers. Returns
    (latency, ttft) arrays in arrival order."""
    from repro.serving.trace import TraceSink
    n = len(prompts)
    prev = ce.trace
    sink = ce.trace = prev if prev is not None else TraceSink()
    ce.steps = ce.active_slot_steps = 0
    t0 = time.perf_counter()
    nxt = 0
    done = set()
    rid2i = {}
    while len(done) < n:
        now = time.perf_counter() - t0
        while nxt < n and arrivals[nxt] <= now:
            rid2i[ce.submit(prompts[nxt], int(gens[nxt]),
                            greedy=greedy)] = nxt
            nxt += 1
        if not ce.pending:
            time.sleep(max(arrivals[nxt] - now, 0.0) + 1e-4)
            continue
        for ev in ce.step():
            if ev.kind == "done":
                done.add(ev.rid)
    lat, ttft = np.zeros(n), np.zeros(n)
    for rid, i in rid2i.items():
        q = sink.query(comp="engine", rid=rid, name="queued")[-1].ts
        lat[i] = sink.query(comp="engine", rid=rid,
                            name="done")[-1].ts - q
        ttft[i] = sink.query(comp="engine", rid=rid,
                             name="first_token")[-1].ts - q
    ce.trace = prev
    return lat, ttft


def _variant_cfgs(mode: str):
    """(row name, reduced config, greedy) for the paged-coverage rows."""
    import dataclasses
    from repro.configs import get_reduced
    out = [
        ("swa", get_reduced("h2o_danube_1_8b"), True),
        ("sampled", get_reduced("qwen25_0_5b"), False),
    ]
    if mode != "quick":
        out += [
            ("int8", dataclasses.replace(get_reduced("qwen25_0_5b"),
                                         kv_quant=True), True),
            ("moe", get_reduced("granite_moe_1b_a400m"), True),
        ]
    return out


def _run_variants(mode: str, prompts, gens):
    """Continuous-only latency rows for swa / int8 / moe / sampled
    configs: the model zoo the slot-paged engine covers since PR 5."""
    import jax
    from repro.models import model
    from repro.serving.engine import ContinuousEngine

    n = 8 if mode == "quick" else len(prompts)
    prompts, gens = prompts[:n], gens[:n]
    for name, cfg, greedy in _variant_cfgs(mode):
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        ce = ContinuousEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN)
        ce.generate(prompts[:2], max_new=2, greedy=greedy)       # warm
        t0 = time.perf_counter()
        # everything arrives at t=0: a pure drain through the shared loop
        lat, ttft = _run_continuous(ce, prompts, gens, np.zeros(n),
                                    greedy=greedy)
        wall = time.perf_counter() - t0
        p50, p95 = np.percentile(lat, [50, 95])
        emit(f"serving.continuous_{name}", p50 * 1e6,
             f"p95_ms={p95 * 1e3:.0f};"
             f"ttft_p50_ms={np.percentile(ttft, 50) * 1e3:.1f};"
             f"wall_s={wall:.2f};"
             f"slot_util={ce.utilisation():.2f};n={len(prompts)}")


def run_prefix(mode="quick", seed=0):
    """TTFT vs shared-prefix ratio (the PR-8 block-table pager).

    For each share in the sweep, every measured prompt starts with
    `share * L` tokens of a common prefix followed by a random suffix. A
    fresh engine per share is seeded with one unmeasured prompt (warming
    the prefix trie and the COW-copy executable), then each measured
    prompt's TTFT (GenResult.prefill_s: chunked prefill + any COW copy)
    is recorded. Shared full pages are mapped instead of recomputed and
    the resumed chunk grid skips the reused span, so p50 TTFT must DROP
    as the share rises — asserted for the 90% vs 0% pair."""
    import jax
    from repro.configs import get_reduced
    from repro.models import model
    from repro.serving.engine import ContinuousEngine

    shares = (0.0, 0.9) if mode == "quick" else (0.0, 0.5, 0.9)
    n = 8 if mode == "quick" else 16
    plen = 96
    rng = np.random.default_rng(seed)
    common = rng.integers(4, 500, plen).astype(np.int32)
    cfg = get_reduced("qwen25_0_5b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))

    def prompt_at(share):
        k = int(share * plen)
        tail = rng.integers(4, 500, plen - k).astype(np.int32)
        return np.concatenate([common[:k], tail]) if k else tail

    p50s = {}
    for share in shares:
        ce = ContinuousEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN)
        ce.warmup()
        prompts = [prompt_at(share) for _ in range(n)]
        # seed pass: registers the common prefix and compiles the COW
        # copy off the measured path (two probes so the second COW-forks)
        ce.generate([prompt_at(share)], max_new=2)
        ce.generate([prompt_at(share)], max_new=2)
        hits0, reused0 = ce.prefix_hits, ce.prefix_tokens_reused
        ttfts = []
        for p in prompts:
            ttfts.append(ce.generate([p], max_new=2)[0].prefill_s)
        p50s[share] = float(np.percentile(ttfts, 50))
        emit(f"serving.prefix_ttft_share{int(share * 100):02d}",
             p50s[share] * 1e6,
             f"hits={ce.prefix_hits - hits0};"
             f"tokens_reused={ce.prefix_tokens_reused - reused0};"
             f"n={n};plen={plen}")
    assert p50s[0.9] < p50s[0.0], (
        f"prefix sharing did not cut TTFT: "
        f"p50@90%={p50s[0.9]:.4f}s >= p50@0%={p50s[0.0]:.4f}s")


def run_chaos(mode="quick", seed=0, trace_export=None):
    """Goodput under a seeded FaultPlan: every request either completes
    within its deadline or is explicitly shed — the emitted row asserts
    the partition (lost == 0) on top of the latency percentiles. With
    `trace_export=PATH` the whole run records into a shared TraceSink
    whose JSONL export feeds tools/trace_check.py (the nightly CI
    artifact)."""
    import jax
    from repro.configs import get_reduced
    from repro.models import model
    from repro.serving.engine import ContinuousEngine
    from repro.serving.faults import FaultPlan, wrap_replicas
    from repro.serving.scheduler import SlotScheduler
    from repro.serving.trace import TraceSink

    n = 16 if mode == "quick" else 48
    prompts, gens = _workload(mode, seed=seed)
    while len(prompts) < n:
        more, mg = _workload(mode, seed=seed + len(prompts))
        prompts, gens = prompts + more, np.concatenate([gens, mg])
    prompts, gens = prompts[:n], gens[:n]

    cfg = get_reduced("qwen25_0_5b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    base = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN)
    base.warmup()
    engines = [base] + [base.clone() for _ in range(2)]
    for e in engines[1:]:
        e.warmup()
    sink = TraceSink() if trace_export else None
    if sink is not None:
        for e in engines:
            e.trace = sink

    plan = FaultPlan.quick(seed)
    sched = SlotScheduler(wrap_replicas(engines, plan), stall_s=1.0,
                          probe_cooldown_s=0.1, deadline_s=60.0,
                          trace=sink)
    t0 = time.perf_counter()
    deadlines = {}
    for i, p in enumerate(prompts):
        # every 5th request gets a tight deadline (exercises shedding)
        d = 0.02 if i % 5 == 4 else 60.0
        deadlines[sched.submit(p, int(gens[i]), deadline_s=d)] = d
    done = sched.run()
    wall = time.perf_counter() - t0

    lat = np.array([c.latency_s for c in done]) if done else np.zeros(1)
    p50, p95 = np.percentile(lat, [50, 95])
    good = sum(1 for c in done if c.latency_s <= deadlines[c.rid])
    cnt = sched.counters
    lost = n - len(done) - len(sched.shed)
    emit("serving.chaos", p50 * 1e6,
         f"p95_ms={p95 * 1e3:.0f};goodput={good}/{n};"
         f"shed={len(sched.shed)};lost={lost};hedges={cnt.hedges};"
         f"drains={cnt.drains};recoveries={cnt.recoveries};"
         f"wall_s={wall:.2f}")
    assert lost == 0, f"{lost} requests silently lost under chaos"
    if sink is not None:
        m = sink.export_jsonl(trace_export)
        emit("serving.chaos_trace", float(m),
             f"path={trace_export};evicted={sink.evicted}")


def run(mode="quick"):
    import jax
    from repro.configs import get_reduced
    from repro.models import model
    from repro.serving.engine import Engine

    prompts, gens = _workload(mode)
    cfg = get_reduced("qwen25_0_5b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_len=MAX_LEN, slots=SLOTS)
    ce = eng.continuous()

    # warm every fixed shape both paths use (bucketed wave prefill at each
    # batch size, chunk-prefill + paged decode for continuous)
    for b in range(1, SLOTS + 1):
        eng.generate([_pad(prompts[0])] * b, max_new=2, continuous=False)
    ce.warmup()

    # calibrate the Poisson rate to ~80% of measured decode capacity
    ce.steps = ce.active_slot_steps = 0
    t0 = time.perf_counter()
    ce.generate(prompts[:SLOTS], max_new=8)
    t_cal = time.perf_counter() - t0
    t_step = t_cal / max(ce.steps, 1)               # engine step wall time
    steps_per_req = np.mean([len(p) // ce.prefill_chunk + 1
                             for p in prompts]) + float(np.mean(gens))
    service_s = steps_per_req * t_step / SLOTS      # per request, amortised
    rate = 0.8 / max(service_s, 1e-4)
    arrivals = _arrivals(len(prompts), rate, seed=0)

    lat_w = _run_wave(eng, prompts, gens, arrivals)
    lat_c, ttft_c = _run_continuous(ce, prompts, gens, arrivals)

    p50w, p95w = np.percentile(lat_w, [50, 95])
    p50c, p95c = np.percentile(lat_c, [50, 95])
    emit("serving.wave", p50w * 1e6,
         f"p95_ms={p95w * 1e3:.0f};n={len(prompts)};rate={rate:.1f}qps")
    emit("serving.continuous", p50c * 1e6,
         f"p95_ms={p95c * 1e3:.0f};"
         f"ttft_p50_ms={np.percentile(ttft_c, 50) * 1e3:.1f};"
         f"slot_util={ce.utilisation():.2f}")
    emit("serving.p95_speedup", (p95w / max(p95c, 1e-9)) * 1e6,
         f"continuous_beats_wave={bool(p95c < p95w)}")

    _run_variants(mode, prompts, gens)
    run_prefix(mode)
    run_chaos(mode)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="quick", choices=["quick", "full"])
    ap.add_argument("--chaos", action="store_true",
                    help="goodput-under-chaos section only")
    ap.add_argument("--prefix", action="store_true",
                    help="shared-prefix TTFT sweep only")
    ap.add_argument("--trace-export", default=None, metavar="PATH",
                    help="with --chaos: export the run's TraceSink as "
                         "JSONL for tools/trace_check.py")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if a.chaos:
        run_chaos(a.mode, a.seed, trace_export=a.trace_export)
    elif a.prefix:
        run_prefix(a.mode, a.seed)
    else:
        run(a.mode)
