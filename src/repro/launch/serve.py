"""Serving driver: the full MobileRAG pipeline on the request-centric API.

  # batched: answer_batch(generate=True) through the RagSession
  PYTHONPATH=src python -m repro.launch.serve --pipeline mobile --questions 16

  # streaming: Poisson arrivals into a live session, per-request latency
  PYTHONPATH=src python -m repro.launch.serve --stream --arrival-qps 4

  # the paper's generator at its published widths (a chip's work)
  PYTHONPATH=src python -m repro.launch.serve --stream --full

  # multi-replica: SlotScheduler over N continuous engines
  PYTHONPATH=src python -m repro.launch.serve --replicas 2

  # serving under fire: per-request deadlines + deterministic chaos
  PYTHONPATH=src python -m repro.launch.serve --replicas 3 --chaos \
      --deadline-s 30

Wires: synthetic corpus -> embedder -> EcoVector -> SCR -> RagSession
(continuous-batching decode on the slot-paged engine; retrieval/SCR of the
next queries overlaps decode of the previous ones). `--deadline-s` bounds
per-request latency (expired requests are shed, their slots freed);
`--max-pending` bounds session admission (overload degrades, then sheds);
`--chaos` wraps each replica in a seeded FaultPlan (serving/faults.py)
and reports goodput = completed-within-deadline / submitted.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import get_config, get_reduced
from repro.data.synthetic import make_qa_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.embedder import HashEmbedder
from repro.serving.rag import PIPELINES, accuracy


def _percentiles(xs):
    if not xs:
        return 0.0, 0.0
    return (float(np.percentile(xs, 50)), float(np.percentile(xs, 95)))


def run_batch(pipe, corpus, args) -> None:
    questions = [e.question for e in corpus.examples[: args.questions]]
    t0 = time.perf_counter()
    answers = pipe.answer_batch(questions, generate=True,
                                max_new=args.max_new)
    wall = time.perf_counter() - t0
    acc = accuracy(pipe, corpus.examples, max_q=args.questions)
    toks = [a.prompt_tokens for a in answers]
    print(f"[serve] {len(answers)} answers in {wall:.2f}s | "
          f"answer-in-context acc={acc:.2f} | "
          f"prompt tokens mean={np.mean(toks):.0f} | "
          f"measured TTFT={np.mean([a.ttft_measured_s for a in answers]):.3f}s | "
          f"model TTFT={np.mean([a.ttft_model_s for a in answers]):.2f}s | "
          f"model energy={np.mean([a.energy_model_j for a in answers]):.2f}J")
    for a in answers[:3]:
        print(f"  docs={a.doc_ids} gen={a.gen_tokens[:8]}")


def run_stream(pipe, corpus, args) -> None:
    """Poisson arrival process into a live RagSession: queries become
    visible to the session at their arrival times while it keeps stepping,
    so retrieval/SCR of late arrivals overlaps decode of early ones."""
    rng = np.random.default_rng(args.seed)
    n = args.questions
    gaps = rng.exponential(1.0 / args.arrival_qps, size=n)
    arrivals = np.cumsum(gaps)
    sink = None
    if args.trace_export:
        from repro.serving.trace import TraceSink
        sink = TraceSink()
    sess = pipe.session(max_new=args.max_new, slots=args.slots,
                        greedy=not args.sample, seed=args.seed,
                        max_pending=args.max_pending,
                        deadline_s=args.deadline_s,
                        trace=sink, slo_s=args.slo_s)
    t0 = time.perf_counter()
    submitted = 0
    latencies = []
    trace = []
    while submitted < n or sess.pending:
        now = time.perf_counter() - t0
        while submitted < n and arrivals[submitted] <= now:
            rid = sess.submit(corpus.examples[submitted].question)
            trace.append((now, rid, "submitted"))
            submitted += 1
        if not sess.pending:
            time.sleep(min(arrivals[submitted] - now, 0.05))
            continue
        for ev in sess.step():
            if ev.kind in ("retrieved", "done", "shed", "failed"):
                trace.append((time.perf_counter() - t0, ev.req_id, ev.kind))
            if ev.kind == "done":
                req = sess.requests[ev.req_id]
                latencies.append(req.latency_s)
    wall = time.perf_counter() - t0
    p50, p95 = _percentiles(latencies)
    eng = sess.engine
    c = sess.counters
    print(f"[serve --stream] {n} requests at ~{args.arrival_qps:.1f} qps "
          f"in {wall:.2f}s | latency p50={p50:.3f}s p95={p95:.3f}s | "
          f"slot util={eng.utilisation():.2f} "
          f"({eng.steps} decode steps x {eng.slots} slots) | "
          f"prefix hits={eng.prefix_hits} "
          f"tokens reused={eng.prefix_tokens_reused} | "
          f"done={c.completed} "
          f"shed={c.shed_deadline + c.shed_overload + c.shed_oversize} "
          f"degraded={c.degraded} failed={c.failed}")
    if args.slo_s is not None:
        print(f"[serve --slo-s {args.slo_s}] "
              f"slo_shed={c.shed_slo} slo_degraded={c.degraded_slo}")
    if sess.trace is not None and args.trace_export:
        m = sess.trace.export_jsonl(args.trace_export)
        print(f"[serve --trace-export] {m} records -> "
              f"{args.trace_export} (check: python tools/trace_check.py "
              f"{args.trace_export})")
    for t, rid, kind in trace[: 3 * 3]:
        print(f"  t={t:6.3f}s req={rid} {kind}")


def run_replicas(pipe, corpus, args) -> None:
    """SlotScheduler over N continuous-engine replicas (slot admission,
    per-slot stall hedging, failover). With `--chaos` each replica is
    wrapped in its seeded FaultPlan sub-schedule and the line reports
    goodput (completed within deadline / submitted)."""
    from repro.serving.scheduler import SlotScheduler
    slm = pipe._ensure_slm()
    engines = [slm.continuous(args.slots)]
    for _ in range(1, args.replicas):
        engines.append(engines[0].clone())
    sink = None
    if args.trace_export:
        from repro.serving.trace import TraceSink
        sink = TraceSink()
        for e in engines:
            e.trace = sink
    if args.chaos:
        from repro.serving.faults import FaultPlan, wrap_replicas
        engines = wrap_replicas(engines, FaultPlan.quick(args.seed))
    sched = SlotScheduler(engines, max_queue=args.max_queue,
                          deadline_s=args.deadline_s,
                          stall_s=2.0 if args.chaos else 30.0,
                          probe_cooldown_s=0.25, trace=sink)
    questions = [e.question for e in corpus.examples[: args.questions]]
    answers = pipe.answer_batch(questions)          # retrieval + SCR
    t0 = time.perf_counter()
    for a in answers:
        sched.submit(slm.encode_prompt(a.prompt, bucket=False),
                     args.max_new)
    completions = sched.run()
    wall = time.perf_counter() - t0
    lat = [c.latency_s for c in completions]
    p50, p95 = _percentiles(lat)
    cnt = sched.counters
    deadline = args.deadline_s or float("inf")
    good = sum(1 for c in completions if c.latency_s <= deadline)
    print(f"[serve --replicas {args.replicas}] {len(completions)} "
          f"completions in {wall:.2f}s | p50={p50:.3f}s p95={p95:.3f}s | "
          f"goodput={good}/{cnt.submitted} | shed={len(sched.shed)} "
          f"degraded={cnt.degraded} hedges={cnt.hedges} "
          f"drains={cnt.drains} recoveries={cnt.recoveries} | "
          f"served per replica={[s.served for s in sched.state]}")
    for c in completions[:3]:
        print(f"  rid={c.rid} replica={c.replica} hedged={c.hedged} "
              f"tokens={c.tokens[:8]}")
    if sink is not None:
        m = sink.export_jsonl(args.trace_export)
        print(f"[serve --trace-export] {m} records -> "
              f"{args.trace_export} (check: python tools/trace_check.py "
              f"{args.trace_export})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pipeline", default="mobile",
                    choices=list(PIPELINES.keys()))
    ap.add_argument("--questions", type=int, default=8)
    ap.add_argument("--docs", type=int, default=150)
    ap.add_argument("--full", action="store_true",
                    help="generate with qwen25_0_5b at its published "
                         "widths (random weights) instead of the reduced "
                         "CPU smoke size")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--stream", action="store_true",
                    help="Poisson arrival process into a live RagSession")
    ap.add_argument("--sample", action="store_true",
                    help="sampled decode (per-request PRNG streams; "
                         "draws are independent of co-residents) instead "
                         "of greedy — --stream path")
    ap.add_argument("--arrival-qps", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline; expired requests are "
                         "shed with their engine slot freed")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="session admission bound (--stream): overload "
                         "degrades past half, sheds at the bound")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="scheduler queue bound (--replicas): "
                         "degrade-then-shed overflow policy")
    ap.add_argument("--chaos", action="store_true",
                    help="wrap each replica in a seeded FaultPlan "
                         "(crashes/stalls/slow steps) — --replicas path")
    ap.add_argument("--slo-s", type=float, default=None,
                    help="per-request latency SLO (--stream): the "
                         "session degrades retrieve_chunk/n_probe/"
                         "max_new from observed p95 stage costs before "
                         "it sheds (docs/OBSERVABILITY.md)")
    ap.add_argument("--trace-export", default=None, metavar="PATH",
                    help="record the run into a TraceSink and export "
                         "JSONL for tools/trace_check.py "
                         "(--stream / --replicas paths)")
    ap.add_argument("--page-size", type=int, default=32,
                    help="KV pool page granularity (positions per page); "
                         "smaller pages share longer prompt prefixes, "
                         "larger ones cut table/gather overhead")
    ap.add_argument("--device-budget", type=float, default=None,
                    help="device-memory budget for the retrieval index "
                         "(DESIGN.md §14): bytes, or a fraction in (0, 1] "
                         "of the all-resident pack. Builds a tiered "
                         "hot/cold EcoVector and forces device retrieval "
                         "so the tiers are exercised")
    args = ap.parse_args()
    enable_compile_cache()

    corpus = make_qa_corpus("squad", n_docs=args.docs,
                            n_questions=args.questions, seed=args.seed)
    emb = HashEmbedder(dim=128)
    gen = get_config if args.full else get_reduced
    pipe_kw = {"gen_cfg": gen("qwen25_0_5b")}
    if args.device_budget is not None:
        pipe_kw.update(device_budget_bytes=args.device_budget,
                       device_retrieval=True)
    pipe = PIPELINES[args.pipeline](corpus.docs, emb, top_k=3, **pipe_kw)
    if hasattr(pipe, "_ensure_slm"):
        # the Engine is built lazily on first use, so the pool page
        # granularity can still be set here
        pipe._ensure_slm().page_size = args.page_size
    print(f"[serve] pipeline={pipe.name} docs={len(corpus.docs)} "
          f"index_build={pipe.build_s:.2f}s")

    if args.stream:
        run_stream(pipe, corpus, args)
    elif args.replicas > 1:
        run_replicas(pipe, corpus, args)
    else:
        run_batch(pipe, corpus, args)

    if args.device_budget is not None:
        idx, s = pipe.index, pipe.index.stats
        hits = s.tier_hot_hits + s.tier_cold_hits
        print(f"[serve --device-budget] hot={len(idx.hot_clusters())} "
              f"cold={len(idx.cold_clusters())} clusters | "
              f"resident={idx.device_resident_bytes()}B "
              f"budget={idx.device_budget_bytes}B "
              f"(all-resident {idx.all_resident_bytes()}B) | "
              f"hot-hit-rate={s.tier_hot_hits / max(hits, 1):.2f} | "
              f"promotions={s.promotions} demotions={s.demotions}")


if __name__ == "__main__":
    main()
