"""Per-request span tracing + SLO admission.

Covers the trace layer as a correctness ORACLE, not just logging:
  - TraceSink semantics: monotone timestamps under clock skew, ring
    eviction accounting, span pairing, JSONL export/load round trip,
    the span tree (`parent` links) and its mirror onto the profiler's
    clock (`TraceAnnotation` carrying each span's `seq`);
  - a traced session's stage spans: their tree, the session -> engine
    request link, KV pages reserved against pages in use, the prefill
    interval; and an untraced session recording and annotating nothing;
  - tools/trace_check.py catches every class of lifecycle violation it
    claims to (order, orphans, double terminals, unclosed spans, page
    leaks, silent fault drops) and passes real engine/session runs —
    including ring-truncated exports and recycled rids;
  - property-based workloads (ragged lengths, seeds, cancels) through
    the checker, with deterministic fallbacks per hypothesis_compat;
  - SLOController: degrade-before-shed ladder from live p95 stage
    costs, never shedding blind, wired through RagSession admission.
"""
import contextlib
import importlib.util
import pathlib

import numpy as np
import pytest
import jax

from hypothesis_compat import given, settings, st

from repro.configs import get_reduced
from repro.models import model
from repro.serving.engine import ContinuousEngine
from repro.serving.trace import SLOController, TraceSink, load_jsonl

_TC = pathlib.Path(__file__).resolve().parent.parent / "tools" \
    / "trace_check.py"
_spec = importlib.util.spec_from_file_location("trace_check", _TC)
trace_check = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_check)


# ------------------------------------------------------------- sink units


def test_sink_timestamps_monotone_under_clock_skew():
    """A clock that jumps backwards must not produce an unordered
    stream: emit() clamps ts to the high-water mark."""
    ticks = iter([5.0, 4.0, 4.5, 6.0])
    sink = TraceSink(clock=lambda: next(ticks))
    for i in range(4):
        sink.emit("bench", "tick", i)
    ts = [r.ts for r in sink.records()]
    assert ts == [5.0, 5.0, 5.0, 6.0]
    assert not trace_check.check_records(sink.records(), complete=False)


def test_sink_ring_eviction_counts():
    sink = TraceSink(capacity=4)
    for i in range(7):
        sink.emit("engine", "token", i)
    assert len(sink) == 4 and sink.evicted == 3
    assert [r.rid for r in sink.records()] == [3, 4, 5, 6]
    assert sink.records()[0].seq == 3     # truncation is detectable


def test_sink_query_durations_percentile():
    clock = {"t": 0.0}
    sink = TraceSink(clock=lambda: clock["t"])
    for i, dur in enumerate((0.01, 0.02, 0.03)):
        sink.emit("engine", "decode_step", ph="B")
        clock["t"] += dur
        sink.emit("engine", "decode_step", ph="E")
        sink.emit("session", "queued", i)
    assert len(sink.query(comp="session")) == 3
    assert len(sink.query(comp="engine", name="decode_step")) == 6
    ds = sink.durations("engine", "decode_step")
    assert np.allclose(ds, [0.01, 0.02, 0.03])
    assert np.isclose(sink.percentile("engine", "decode_step", 50), 0.02)
    assert np.isclose(sink.percentile("engine", "decode_step", 95,
                                      window=2), 0.03)
    assert sink.percentile("engine", "prefill_chunk", default=7.0) == 7.0


def test_jsonl_roundtrip(tmp_path):
    sink = TraceSink()
    sink.emit("engine", "queued", 0, src="e0", prompt_len=8)
    with sink.span("engine", "prefill_chunk", 0, src="e0", n=4):
        pass
    path = tmp_path / "t.jsonl"
    assert sink.export_jsonl(path) == 3
    back = load_jsonl(path)
    assert [r.to_dict() for r in back] \
        == [r.to_dict() for r in sink.records()]
    assert back[0].attrs["prompt_len"] == 8


class _Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`: records each
    annotation's name and metadata."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **meta):
        self.seen.append((name, meta))
        return contextlib.nullcontext()


def test_sink_span_tree_and_annotation_mirror(monkeypatch):
    """Nested spans name the innermost open span as `parent` (-1 at the
    top), close children first, and each enters one profiler annotation
    named comp/name with the B record's seq."""
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    sink = TraceSink()
    with sink.span("session", "step", src="s0") as step:
        with sink.span("session", "retrieve", src="s0", n=1) as ret:
            with sink.span("rag", "embed", n=1) as emb:
                pass
        with sink.span("engine", "decode_step", src="e0") as dec:
            pass
    with sink.span("session", "step", src="s0") as top:
        pass
    assert [b.attrs["parent"] for b in (step, ret, emb, dec, top)] \
        == [-1, step.seq, ret.seq, step.seq, -1]
    assert ann.seen == [(f"{b.comp}/{b.name}", {"seq": b.seq})
                        for b in (step, ret, emb, dec, top)]
    recs = sink.records()
    assert [(r.name, r.ph) for r in recs] == [
        ("step", "B"), ("retrieve", "B"), ("embed", "B"), ("embed", "E"),
        ("retrieve", "E"), ("decode_step", "B"), ("decode_step", "E"),
        ("step", "E"), ("step", "B"), ("step", "E")]
    assert not trace_check.check_records(recs)
    # an exception inside a span still closes it, and the stack unwinds
    with pytest.raises(ValueError):
        with sink.span("session", "step", src="s0"):
            raise ValueError
    with sink.span("session", "step", src="s0") as after:
        pass
    assert after.attrs["parent"] == -1
    assert not trace_check.check_records(sink.records())


# ------------------------------------------------- checker catches badness


def _r(seq, comp, name, rid=-1, ph="I", src="e0", **attrs):
    return {"seq": seq, "ts": float(seq), "comp": comp, "src": src,
            "rid": rid, "name": name, "ph": ph, "attrs": attrs}


def _good_chain(rid=0, seq0=0):
    return [
        _r(seq0 + 0, "engine", "queued", rid),
        _r(seq0 + 1, "engine", "admitted", rid),
        _r(seq0 + 2, "engine", "prefill_chunk", rid, ph="B"),
        _r(seq0 + 3, "engine", "prefill_chunk", rid, ph="E"),
        _r(seq0 + 4, "engine", "first_token", rid),
        _r(seq0 + 5, "engine", "token", rid),
        _r(seq0 + 6, "engine", "done", rid),
    ]


def test_checker_accepts_good_chain_and_recycled_rid():
    recs = _good_chain(0) + _good_chain(0, seq0=7)   # rid reuse is legal
    assert trace_check.check_records(recs) == []


@pytest.mark.parametrize("mutate, needle", [
    # token stream before the first_token marker
    (lambda c: [c[0], c[1], _r(9, "engine", "token", 0),
                c[4], c[6]], "before"),
    # admitted twice terminal twice
    (lambda c: c + [_r(9, "engine", "done", 0)], "after terminal"),
    # lifecycle continues past a cancel
    (lambda c: c[:5] + [_r(9, "engine", "cancelled", 0),
                        _r(10, "engine", "token", 0)], "after terminal"),
    # first event is not queued (and the stream is NOT truncated:
    # seqs renumbered from 0 so the head can't be a ring eviction)
    (lambda c: [dict(r, seq=i, ts=float(i))
                for i, r in enumerate(c[1:])], "expected 'queued'"),
    # no terminal at all in a complete trace
    (lambda c: c[:5], "no terminal"),
    # E without a B
    (lambda c: [c[0], c[1], c[3], c[4], c[6]], "E without open B"),
    # B never closed
    (lambda c: [c[0], c[1], c[2], c[4], c[6]], "never closed"),
    # seq order broken
    (lambda c: [c[0], dict(c[1], seq=0)], "seq not increasing"),
    # time goes backwards
    (lambda c: [c[0], dict(c[1], ts=-1.0)], "ts went backwards"),
])
def test_checker_flags_lifecycle_violations(mutate, needle):
    viol = trace_check.check_records(mutate(_good_chain()))
    assert viol and any(needle in v for v in viol), viol


def _tree(*rows):
    """Records from (name, ph, parent) rows; a session request 0 queued
    and condensed first, so session-scoped spans may follow."""
    head = [_r(0, "session", "queued", 0, src="s0"),
            _r(1, "session", "retrieved", 0, src="s0"),
            _r(2, "session", "condensed", 0, src="s0")]
    out = list(head)
    for i, (comp, name, ph, rid, attrs) in enumerate(rows, len(head)):
        out.append(_r(i, comp, name, rid, ph=ph, src="s0", **attrs))
    return out


def test_checker_accepts_a_span_tree():
    recs = _tree(("session", "step", "B", -1, {"parent": -1}),
                 ("session", "encode", "B", 0, {"parent": 3}),
                 ("engine", "queued", "I", 0,
                  {"parent_src": "s0", "parent_rid": 0}),
                 ("session", "encode", "E", 0, {}),
                 ("session", "step", "E", -1, {}),
                 ("engine", "admitted", "I", 0, {}),
                 ("engine", "prefill_readback", "B", 0, {"parent": -1}),
                 ("engine", "prefill_readback", "E", 0, {}),
                 ("engine", "first_token", "I", 0, {}),
                 ("engine", "done", "I", 0, {}),
                 ("session", "done", "I", 0, {}))
    assert trace_check.check_records(recs) == []


@pytest.mark.parametrize("rows, needle", [
    # a span name the program does not open
    ((("engine", "warp", "B", -1, {}), ("engine", "warp", "E", -1, {})),
     "unknown span"),
    # the last chunk's readback of a request never admitted
    ((("engine", "queued", "I", 0, {}),
      ("engine", "prefill_readback", "B", 0, {}),
      ("engine", "prefill_readback", "E", 0, {})), "before any of"),
    # encoding a session request that was never condensed
    ((("session", "queued", "I", 1, {}),
      ("session", "encode", "B", 1, {}),
      ("session", "encode", "E", 1, {})), "before any of"),
    # a parent other than the innermost open span
    ((("session", "step", "B", -1, {"parent": -1}),
      ("session", "retrieve", "B", -1, {"parent": -1}),
      ("session", "retrieve", "E", -1, {}),
      ("session", "step", "E", -1, {})), "not the innermost"),
    # a parent that closes while its child is open
    ((("session", "step", "B", -1, {"parent": -1}),
      ("session", "retrieve", "B", -1, {"parent": 3}),
      ("session", "step", "E", -1, {}),
      ("session", "retrieve", "E", -1, {})), "opened inside it"),
    # an engine request caused by a session request never queued
    ((("engine", "queued", "I", 0,
       {"parent_src": "s0", "parent_rid": 7}),), "never queued"),
])
def test_checker_flags_span_tree_violations(rows, needle):
    viol = trace_check.check_records(_tree(*rows), complete=False)
    assert viol and any(needle in v for v in viol), viol


def test_checker_flags_pager_and_replica_violations():
    leak = [_r(0, "pager", "page_stats", total=8, free=2, mapped_refs=5,
               retained=3, inflight=0)]
    viol = trace_check.check_records(leak)
    assert any("leak" in v for v in viol), viol
    # same stats while requests are still in flight: fine
    busy = [dict(leak[0], attrs=dict(leak[0]["attrs"], inflight=2))]
    assert not trace_check.check_records(busy)
    bad_stats = [_r(0, "pager", "page_stats", total=8, free=9,
                    mapped_refs=2, retained=3, inflight=1)]
    assert len(trace_check.check_records(bad_stats)) == 2
    recover = [_r(0, "sched", "recover", src="q0", replica=1)]
    assert any("without" in v for v in
               trace_check.check_records(recover))
    ok = [_r(0, "sched", "drain", src="q0", replica=1),
          _r(1, "sched", "recover", src="q0", replica=1)]
    assert not trace_check.check_records(ok)


def test_checker_flags_silently_dropped_crash():
    recs = _good_chain()[:5] + [
        _r(9, "chaos", "injected", kind="replica_crash", inflight=1),
        _r(10, "engine", "done", 0),
    ]
    viol = trace_check.check_records(recs)
    assert any("no 'cancelled'" in v for v in viol), viol
    # the same crash followed by the cancel chain is well-formed
    recs[-1] = _r(10, "engine", "cancelled", 0)
    assert not trace_check.check_records(recs)


def test_checker_grandfathers_ring_truncation():
    """An export whose head was evicted (first seq > 0) must not flag
    requests whose beginnings fell off the buffer."""
    mid = [_r(50, "engine", "first_token", 3),
           _r(51, "engine", "token", 3),
           _r(52, "engine", "done", 3)]
    assert not trace_check.check_records(mid)
    # but the same stream starting at seq 0 is a violation
    fresh = [dict(r, seq=r["seq"] - 50, ts=float(r["seq"] - 50))
             for r in mid]
    assert trace_check.check_records(fresh)


# --------------------------------------------------------- SLO controller


def _seeded_sink():
    """Synthetic stage history: retrieve 0.10s for 2 queries (0.05/q),
    prefill chunk 0.02s, decode step 0.01s."""
    clock = {"t": 0.0}
    sink = TraceSink(clock=lambda: clock["t"])

    def span(comp, name, dur, rid=-1, **attrs):
        sink.emit(comp, name, rid, ph="B", **attrs)
        clock["t"] += dur
        sink.emit(comp, name, rid, ph="E")

    span("session", "retrieve", 0.10, n=2)
    span("engine", "prefill_chunk", 0.02, rid=0)
    span("engine", "decode_step", 0.01)
    return sink


def test_slo_stage_costs_and_estimate():
    c = SLOController(_seeded_sink())
    costs = c.stage_costs()
    assert np.isclose(costs["retrieve_per_query_s"], 0.05)
    assert np.isclose(costs["prefill_chunk_s"], 0.02)
    assert np.isclose(costs["decode_step_s"], 0.01)
    # 0.05 + 2*0.02 + 10*0.01
    assert np.isclose(c.estimate(10), 0.19)


def test_slo_ladder_degrades_before_shedding():
    c = SLOController(_seeded_sink())
    # plenty of budget: admit untouched
    p = c.plan(1.0, 16, 4, 4)
    assert p.action == "admit" and p.max_new == 16 and p.n_probe == 4
    # tight budget: degrade — clamp max_new to fit, halve chunk + probes
    p = c.plan(0.15, 16, 4, 4)
    assert p.action == "degrade"
    assert p.max_new == 6                 # (0.15 - 0.09) / 0.01
    assert p.retrieve_chunk == 2 and p.n_probe == 2
    # budget below even the floor (1 token, 0.10s): shed
    p = c.plan(0.05, 16, 4, 4)
    assert p.action == "shed"
    # floors are respected on the way down
    p = c.plan(0.101, 16, 1, 1)
    assert p.action == "degrade"
    assert p.max_new == 1 and p.retrieve_chunk == 1 and p.n_probe == 1


def test_slo_never_sheds_blind():
    """No samples in the window (or no budget at all): always admit."""
    c = SLOController(TraceSink())
    assert c.plan(1e-9, 16, 4, 4).action == "admit"
    c2 = SLOController(_seeded_sink())
    assert c2.plan(None, 16, 4, 4).action == "admit"


# ------------------------------------------------------- real engine runs


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_reduced("qwen25_0_5b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run_workload(cfg, params, lens, cancel_at, seed):
    """Ragged prompts through a traced engine, cancelling a subset
    mid-flight; returns (engine, sink, rids)."""
    sink = TraceSink()
    ce = ContinuousEngine(cfg, params, slots=2, max_len=96, trace=sink)
    rng = np.random.default_rng(seed)
    rids = [ce.submit(rng.integers(4, 500, n).astype(np.int32),
                      max_new=2 + i % 3, greedy=bool(i % 2), seed=seed)
            for i, n in enumerate(lens)]
    for i in cancel_at:
        ce.cancel(rids[i % len(rids)])
    steps = 0
    while ce.pending:
        ce.step()
        steps += 1
        assert steps < 10_000
    return ce, sink, rids


def _assert_trace_oracle(ce, sink, rids):
    viol = trace_check.check_records(sink.records())
    assert viol == [], viol
    recs = [r.to_dict() for r in sink.records()]
    queued = {r["rid"] for r in recs
              if r["comp"] == "engine" and r["name"] == "queued"}
    assert queued == set(rids)
    # exactly one terminal per rid, and page accounting reconciles with
    # the live engine
    terms = [r for r in recs if r["comp"] == "engine"
             and r["name"] in ("done", "shed", "cancelled")]
    assert sorted(t["rid"] for t in terms) == sorted(rids)
    st = ce.page_stats()
    last = trace_check.last_page_stats(recs)
    assert last["mapped_refs"] == st.mapped_refs
    assert last["retained"] == st.retained
    assert last["inflight"] == 0


def test_engine_trace_is_clean_and_reconciles(dense_setup):
    cfg, params = dense_setup
    ce, sink, rids = _run_workload(cfg, params,
                                   lens=(16, 40, 9, 33, 24),
                                   cancel_at=(1, 3), seed=0)
    _assert_trace_oracle(ce, sink, rids)
    recs = [r.to_dict() for r in sink.records()]
    # cancelled requests really terminate as cancelled, and emit nothing
    # afterwards (checked structurally by the oracle; spot-check kinds)
    kinds = {r["rid"]: r["name"] for r in recs if r["comp"] == "engine"
             and r["name"] in ("done", "cancelled")}
    assert kinds[rids[1]] == "cancelled" and kinds[rids[3]] == "cancelled"
    assert kinds[rids[0]] == "done"
    # prefill/decode spans all closed, with positive durations
    assert all(d > 0 for d in sink.durations("engine", "prefill_chunk"))
    assert all(d > 0 for d in sink.durations("engine", "decode_step"))


def test_oversize_and_prefix_hit_appear_in_trace(dense_setup):
    cfg, params = dense_setup
    sink = TraceSink()
    ce = ContinuousEngine(cfg, params, slots=2, max_len=96, trace=sink)
    rng = np.random.default_rng(3)
    p = rng.integers(4, 500, 50).astype(np.int32)
    big = rng.integers(4, 500, ce.table_width * ce.page_size) \
        .astype(np.int32)
    ce.submit(p, max_new=4)
    shed_rid = ce.submit(big, max_new=8)
    while ce.pending:
        ce.step()
    ce.submit(p, max_new=4)               # second pass: prefix hit
    while ce.pending:
        ce.step()
    viol = trace_check.check_records(sink.records())
    assert viol == [], viol
    recs = [r.to_dict() for r in sink.records()]
    sheds = [r for r in recs if r["name"] == "shed"]
    assert [s["rid"] for s in sheds] == [shed_rid]
    assert sheds[0]["attrs"]["reason"] == "oversize"
    hits = [r for r in recs if r["comp"] == "pager"
            and r["name"] == "prefix_hit"]
    assert hits and hits[0]["attrs"]["matched"] >= 32


# deterministic fallback workloads mirror the property test's domain
_WORKLOADS = [
    ((8, 21, 34, 47), (0,), 1),
    ((60, 5, 5, 60, 30), (2, 4), 2),
    ((12,), (), 3),
]


@pytest.mark.parametrize("lens, cancel_at, seed", _WORKLOADS)
def test_workload_trace_invariants_deterministic(dense_setup, lens,
                                                 cancel_at, seed):
    cfg, params = dense_setup
    ce, sink, rids = _run_workload(cfg, params, lens, cancel_at, seed)
    _assert_trace_oracle(ce, sink, rids)


@settings(max_examples=5, deadline=None)
@given(st.lists(st.integers(4, 70), min_size=1, max_size=6),
       st.lists(st.integers(0, 5), max_size=2),
       st.integers(0, 100))
def test_workload_trace_invariants_property(lens, cancel_at, seed):
    cfg = get_reduced("qwen25_0_5b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    ce, sink, rids = _run_workload(cfg, params, lens, cancel_at, seed)
    _assert_trace_oracle(ce, sink, rids)


# --------------------------------------------------------- session + SLO


@pytest.fixture(scope="module")
def corpus():
    from repro.data.synthetic import make_qa_corpus
    return make_qa_corpus("squad", n_docs=50, n_questions=16, seed=0)


def _mobile(corpus):
    from repro.serving.embedder import HashEmbedder
    from repro.serving.rag import MobileRAG
    return MobileRAG(corpus.docs, HashEmbedder(dim=96), top_k=3)


def test_session_trace_full_lifecycle(corpus, tmp_path):
    """A traced RagSession run is checker-clean end to end (session +
    engine + pager components share one sink), and the export survives
    the CLI checker."""
    pipe = _mobile(corpus)
    sink = TraceSink()
    sess = pipe.session(max_new=4, slots=2, retrieve_chunk=2, trace=sink)
    out = sess.run([e.question for e in corpus.examples[:4]])
    assert all(a is not None for a in out)
    viol = trace_check.check_records(sink.records())
    assert viol == [], viol
    recs = [r.to_dict() for r in sink.records()]
    by_name = {}
    for r in recs:
        if r["comp"] == "session" and r["ph"] != "E":
            by_name.setdefault(r["name"], []).append(r["rid"])
    assert sorted(by_name["queued"]) == [0, 1, 2, 3]
    assert sorted(by_name["done"]) == [0, 1, 2, 3]
    assert set(by_name["retrieved"]) == set(by_name["condensed"])
    # retrieve spans carry the fused chunk size for per-query costing
    bs = [r for r in recs if r["name"] == "retrieve" and r["ph"] == "B"]
    assert bs and all(1 <= b["attrs"]["n"] <= 2 for b in bs)
    path = tmp_path / "session.jsonl"
    sink.export_jsonl(path)
    assert trace_check.main([str(path)]) == 0


def test_session_slo_sheds_after_learning_costs(corpus):
    """SLO admission learns stage costs from the first (blindly
    admitted) chunk, then sheds requests whose budget can't even cover
    the floor configuration — and the shed chains stay checker-clean."""
    pipe = _mobile(corpus)
    sink = TraceSink()
    sess = pipe.session(max_new=4, slots=2, retrieve_chunk=2,
                        trace=sink, slo_s=1e-6)
    first = [sess.submit(e.question) for e in corpus.examples[:2]]
    while sess.pending:
        sess.step()
    # no samples yet when the first chunk was planned: admitted blind
    assert all(sess.requests[r].state == "done" for r in first)
    assert sess.counters.shed_slo == 0
    later = [sess.submit(e.question) for e in corpus.examples[2:4]]
    while sess.pending or sess._events_out:
        sess.step()
    assert all(sess.requests[r].state == "shed" for r in later)
    assert sess.counters.shed_slo == 2
    viol = trace_check.check_records(sink.records())
    assert viol == [], viol
    shed = [r for r in sink.records()
            if r.comp == "session" and r.name == "shed"]
    assert {r.attrs["reason"] for r in shed} == {"slo"}


def test_session_slo_degrade_reduces_n_probe(corpus, monkeypatch):
    """The degrade rung really lowers the pipeline's probe width for the
    planned chunk and restores it afterwards — through a wrapper chain,
    exercising the `.inner` walk."""
    from repro.serving.faults import ChaosPipeline, FaultPlan
    pipe = _mobile(corpus)
    wrapped = ChaosPipeline(pipe, FaultPlan(seed=0))   # no faults @ rate 0
    sink = TraceSink()
    sess = wrapped.session(max_new=4, slots=2, retrieve_chunk=2,
                           trace=sink, slo_s=30.0)
    seen = []
    orig = type(pipe)._retrieve_batch

    def spy(self, qvs, k):
        seen.append(self.n_probe)
        return orig(self, qvs, k)

    monkeypatch.setattr(type(pipe), "_retrieve_batch", spy)
    # prime the cost window
    sess.run([corpus.examples[0].question])
    assert seen == [4]
    # force the planner into the degrade rung for the next chunk
    monkeypatch.setattr(
        sess._slo, "plan",
        lambda budget, mx, ch, np_, **kw: __import__(
            "repro.serving.trace", fromlist=["SLOPlan"]).SLOPlan(
                "degrade", mx, ch, 2, 0.0))
    sess.run([corpus.examples[1].question])
    assert seen[-1] == 2                  # degraded probe width applied
    assert pipe.n_probe == 4              # and restored after the chunk
    assert sess.counters.degraded_slo == 1


@pytest.fixture(scope="module")
def traced_session(corpus):
    """One traced session run of four queries, two a retrieval chunk,
    over two slots, with the profiler annotations recorded."""
    ann = _Annotations()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.profiler, "TraceAnnotation", ann)
        pipe = _mobile(corpus)
        sink = TraceSink()
        sess = pipe.session(max_new=4, slots=2, retrieve_chunk=2,
                            trace=sink)
        sess.run([e.question for e in corpus.examples[:4]])
    return sess, sink.records(), ann.seen


def test_session_span_tree(traced_session):
    """Every stage span of a session run nests as the table in
    docs/OBSERVABILITY.md says: each B's parent is open when it begins,
    and every span closes before the span it opened in."""
    sess, recs, _ = traced_session
    assert trace_check.check_records(recs) == []
    by_seq = {r.seq: r for r in recs}
    # the engine's warm-up at session construction runs untouched by the
    # session: its spans are top-level
    first_step = min(r.seq for r in recs if r.name == "step")
    stack, parent_name = [], {}
    for r in recs:
        if r.ph == "B":
            p = r.attrs["parent"]
            assert p == (stack[-1].seq if stack else -1)
            if r.seq < first_step:
                assert r.comp == "engine"
            else:
                parent_name.setdefault(f"{r.comp}/{r.name}", set()).add(
                    "-" if p < 0
                    else f"{by_seq[p].comp}/{by_seq[p].name}")
            stack.append(r)
        elif r.ph == "E":
            top = stack.pop()
            assert (top.comp, top.src, top.rid, top.name) \
                == (r.comp, r.src, r.rid, r.name)
    assert not stack
    assert parent_name == {
        "session/step": {"-"},
        "session/retrieve": {"session/step"},
        "rag/embed": {"session/retrieve"},
        "rag/search": {"session/retrieve"},
        "rag/scr": {"session/retrieve"},
        "rag/prompt": {"session/retrieve"},
        "session/encode": {"session/step"},
        "engine/admit": {"session/step"},
        "engine/prefill_chunk": {"session/step"},
        "engine/prefill_readback": {"session/step"},
        "engine/decode_step": {"session/step"},
        "engine/decode_readback": {"engine/decode_step"},
    }


def test_session_engine_link(traced_session):
    """Each engine request names the session request that caused it,
    and that request rode in the `rids` of a retrieve span before it."""
    sess, recs, _ = traced_session
    first_step = min(r.seq for r in recs if r.name == "step")
    recs = [r for r in recs if r.seq >= first_step]   # past warm-up
    retrieved = []
    links = {}
    for r in recs:
        if r.comp == "session" and r.name == "retrieve" and r.ph == "B":
            assert len(r.attrs["rids"]) == r.attrs["n"]
            retrieved += r.attrs["rids"]
        if r.comp == "engine" and r.name == "queued":
            assert r.attrs["parent_src"] == sess.trace_src
            assert r.attrs["parent_rid"] in retrieved
            links[r.rid] = r.attrs["parent_rid"]
    assert sorted(retrieved) == [0, 1, 2, 3]
    assert sorted(links.values()) == [0, 1, 2, 3]
    admits = [r for r in recs if r.name == "admit" and r.ph == "B"]
    assert sum(b.attrs["admitted"] for b in admits) == 4


def test_decode_steps_count_pages(traced_session):
    """Decode steps carry the KV pages mapped to decoding slots and the
    pages holding their K/V: never more in use than reserved."""
    _, recs, _ = traced_session
    steps = [r.attrs for r in recs
             if r.name == "decode_step" and r.ph == "B"]
    assert steps
    assert all(0 < a["pages_live"] <= a["pages_reserved"] for a in steps)


def test_spans_mirrored_as_annotations(traced_session):
    """One profiler annotation per span, named comp/name, carrying the
    seq of the span's B record."""
    _, recs, seen = traced_session
    assert seen == [(f"{r.comp}/{r.name}", {"seq": r.seq})
                    for r in recs if r.ph == "B"]


def test_prefill_s_is_admission_to_first_token(traced_session):
    """A request's prefill_s is its first_token record's time less its
    admitted record's: the interval `prefill_ms_per_request` reads."""
    _, recs, _ = traced_session
    at = {}
    for r in recs:
        if r.comp == "engine" and r.name in ("admitted", "first_token"):
            at[(r.rid, r.name)] = r.ts
        if r.comp == "engine" and r.name == "done":
            assert r.attrs["prefill_s"] == (at[(r.rid, "first_token")]
                                            - at[(r.rid, "admitted")])
            assert r.attrs["prefill_s"] > 0


def test_untraced_session_records_and_annotates_nothing(corpus,
                                                        monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    pipe = _mobile(corpus)
    sess = pipe.session(max_new=4, slots=2, retrieve_chunk=2)
    out = sess.run([e.question for e in corpus.examples[:2]])
    assert all(a is not None and a.ttft_measured_s > 0 for a in out)
    assert sess.trace is None and sess.engine.trace is None
    assert pipe.trace is None
    assert ann.seen == []
