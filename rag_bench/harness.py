"""Build one cell's serving stack, warm it, drive its window, and collect
what the window produced.

Everything runs in one process, because a chip belongs to one process at
a time. The stack is the program's own serving path: `MobileRAG` with
device retrieval (EcoVector `route_and_scan`, SCR `scr_select`) feeding a
`RagSession` over the paged `ContinuousEngine`. The harness only submits
requests, steps the session, and records what the client sees.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional
from unittest import mock

import numpy as np

from rag_bench import traffic

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent
CACHE = BENCH_DIR / "cache"
DRAIN_LIMIT_S = 60.0     # an answer due in the window may come this late


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One `workloads` entry of BENCHMARK.json with the files it names:
    the configuration, the traffic mix, and the cell's own file (its
    fixed rate and its correctness limits)."""
    name: str
    chips: int
    config: dict
    mix: dict
    params: dict


def load_cell(name: str, bench_file: Path = CHECKOUT / "BENCHMARK.json"
              ) -> Cell:
    bench = load_json(bench_file)
    wl = [w for w in bench["workloads"] if w["name"] == name]
    if not wl:
        raise SystemExit(f"rag_bench: no workload {name!r} in {bench_file}")
    wl = wl[0]
    conf = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    root = bench_file.parent
    return cell_from_files(name, wl["config"], wl["traffic"],
                           int(wl["chips"]), root / conf["file"],
                           root / BENCH_DIR.name)


def cell_from_files(name: str, config: str, traffic_name: str, chips: int,
                    config_file: Optional[Path] = None,
                    here: Path = BENCH_DIR) -> Cell:
    """A cell from its files alone (also one that BENCHMARK.json does not
    list, for the diagnostics in calibrate.py)."""
    return Cell(name=name, chips=chips,
                config=load_json(config_file
                                 or here / "configs" / f"{config}.json"),
                mix=load_json(here / "traffic" / f"{traffic_name}.json"),
                params=load_json(here / "cells" / f"{name}.json"))


def sub_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for the program, derived from the run's `--seed`
    (which may exceed 32 bits)."""
    return int(traffic.rng_for(seed, stream).integers(0, 2 ** 31 - 1))


def configure_jax(cache_dir: Path = CACHE / "jax") -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however short its compile, so that only a
    checkout's first run compiles."""
    import jax
    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def model_config(conf: dict):
    """The program's ModelConfig for the configuration file's generator,
    checked against the published sizes the file states: a program
    config that has drifted from them is an error, not a smaller cell."""
    from repro.configs import get_config
    cfg = get_config(conf["arch"])
    m = conf["model"]
    want = {"d_model": m["hidden_size"], "num_layers": m["num_hidden_layers"],
            "num_heads": m["num_attention_heads"],
            "num_kv_heads": m["num_key_value_heads"],
            "resolved_head_dim": m["head_dim"],
            "vocab_size": m["vocab_size"],
            "tie_embeddings": m["tie_word_embeddings"],
            "rope_theta": float(m["rope_theta"])}
    if "num_local_experts" in m:
        want.update({"moe.num_experts": m["num_local_experts"],
                     "moe.top_k": m["num_experts_per_tok"],
                     "moe.expert_d_ff": m["intermediate_size"]})
    else:
        want["d_ff"] = m["intermediate_size"]
    for key, val in want.items():
        got = cfg
        for part in key.split("."):
            got = getattr(got, part)
        if got != val:
            raise SystemExit(f"rag_bench: {conf['arch']} {key}={got}, but "
                             f"the configuration file says {val}")
    return cfg


def serve_weights(slm, conf: dict, seed: int) -> None:
    """Have the generator serve the benchmark's weights for `seed`
    (weights.py): float32 masters holding bfloat16 values, which the
    program keeps as it keeps its own and casts to bfloat16 in every
    call. `SLM` takes no weights, so its first use runs with
    `model.init_params` answering with these, after they are checked
    against the program's declared weight tree; the rest of that first
    use (its Engine, its tokenizer) is the program's own."""
    import jax
    import jax.numpy as jnp
    from repro.models import model
    from rag_bench import reference, weights
    params = weights.nested(weights.make(reference.Arch.from_config(conf),
                                         seed, jnp.float32))
    want = model.param_shapes(slm.cfg)
    got = jax.eval_shape(lambda: params)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or [(w.shape, w.dtype) for w in jax.tree.leaves(want)]
            != [(g.shape, g.dtype) for g in jax.tree.leaves(got)]):
        raise SystemExit("rag_bench: the benchmark's weight tree does not "
                         "match the program's for " + slm.cfg.name)
    jax.block_until_ready(params)
    with mock.patch.object(model, "init_params",
                           lambda *a, **k: params):
        _ = slm.tokenizer       # SLM's first use builds its engine


def corpus(conf: dict):
    from repro.data.synthetic import make_qa_corpus
    c = conf["corpus"]
    return make_qa_corpus(c["style"], n_docs=c["docs"],
                          n_questions=c["questions"], seed=c["data_seed"])


@dataclass
class Stack:
    """The system under test and what the harness hangs on it."""
    pipe: object
    slm: object
    sess: object
    sink: Optional[object]
    questions: List[str]
    timings: Dict[str, float]
    retrievals: List[tuple] = field(default_factory=list)
    selects: List[tuple] = field(default_factory=list)


def build(conf: dict, mix: dict, seed: int, *, trace: bool,
          state_root: Path = CACHE / "index") -> Stack:
    """Corpus and index (from this checkout's snapshot after its first
    run), the generator with weights from the seed, and a session whose
    engine has compiled its programs. Times each part."""
    from repro.serving.embedder import HashEmbedder
    from repro.serving.rag import MobileRAG
    from repro.serving.slm import SLM
    from repro.serving.trace import TraceSink
    t = {}
    t0 = time.perf_counter()
    qa = corpus(conf)
    c, r, e = conf["corpus"], conf["retrieval"], conf["engine"]
    key = hashlib.sha256(json.dumps([c, r["embed_dim"]], sort_keys=True)
                         .encode()).hexdigest()[:16]
    emb = HashEmbedder(dim=r["embed_dim"], seed=c["data_seed"])
    pipe = MobileRAG(qa.docs, emb, top_k=r["top_k"], device_retrieval=True,
                     retrieval_state=str(state_root / key))
    pipe.n_probe = r["n_probe"]
    t["index_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    cfg = model_config(conf)
    slm = SLM(cfg, max_prompt=e["max_prompt"],
              max_new=mix["answer_tokens"]["max"], page_size=e["page_size"])
    serve_weights(slm, conf, sub_seed(seed, "weights"))
    pipe.gen_cfg = cfg
    pipe._slm_engine = slm          # what `_ensure_slm` hands the session
    t["weights_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    sink = TraceSink(capacity=1 << 21) if trace else None
    sess = pipe.session(max_new=mix["answer_tokens"]["max"],
                        slots=e["slots"], retrieve_chunk=e["retrieve_chunk"],
                        greedy=True, seed=sub_seed(seed, "sampling"),
                        trace=sink)
    t["compile_s"] = time.perf_counter() - t0
    return Stack(pipe, slm, sess, sink, [x.question for x in qa.examples], t)


def warm(stack: Stack, conf: dict, mix: dict, seed: int) -> None:
    """Run every program the window runs, at every batch it can take:
    retrieval and SCR at each batch size up to the session's chunk, then
    two passes of one request per slot through the session (chunk
    prefill, decode, sampling; the second pass finds the first pass's
    prompts in the prefix cache and copies their shared page)."""
    t0 = time.perf_counter()
    chunk = conf["engine"]["retrieve_chunk"]
    slots = conf["engine"]["slots"]
    reqs = traffic.warmup_requests(mix, stack.questions, seed,
                                   max(chunk, 2 * slots))
    for b in range(1, chunk + 1):
        stack.pipe.answer_batch([r.question for r in reqs[:b]])
    sess = stack.sess
    for part in (reqs[:slots], reqs[slots:2 * slots]):
        for r in part:
            sess.submit(r.question, max_new=r.max_new)
        while sess.pending:
            sess.step()
    stack.timings["warmup_s"] = time.perf_counter() - t0


def record_outputs(stack: Stack) -> Callable[[], None]:
    """Keep what the timed path's retrieval and SCR calls return, with
    their inputs and the time of the call, for the checks and the kernel
    counts. Returns the function that stops recording."""
    from repro.kernels import ops
    index = stack.pipe.index
    search = index.search_device_batched
    select = ops.scr_select

    def rec_search(q, k=10, n_probe=4, **kw):
        ids, dists = search(q, k=k, n_probe=n_probe, **kw)
        stack.retrievals.append((time.perf_counter(),
                                 np.array(q, np.float32), np.array(ids),
                                 np.array(dists), k, n_probe))
        return ids, dists

    def rec_select(q, data, lens, doc_ids, **kw):
        scores, wins = select(q, data, lens, doc_ids, **kw)
        stack.selects.append((time.perf_counter(), np.array(q, np.float32),
                              np.array(doc_ids), np.asarray(scores),
                              np.asarray(wins)))
        return scores, wins

    index.search_device_batched = rec_search
    ops.scr_select = rec_select

    def stop():
        del index.search_device_batched
        ops.scr_select = select
    return stop


@dataclass
class ReqLog:
    """What the client saw of one request (perf_counter seconds)."""
    question: str
    max_new: int
    due: float
    submit: float
    tokens: List[float] = field(default_factory=list)
    end: Optional[float] = None
    state: str = "pending"


@dataclass
class WindowLog:
    start: float
    end: float
    reqs: Dict[int, ReqLog]
    traced: Optional[tuple] = None      # perf_counter span of the device trace


class _Annotate:
    """`jax.profiler.TraceAnnotation` in a traced run, nothing otherwise."""

    def __init__(self, on: bool):
        import jax
        self._ann = jax.profiler.TraceAnnotation if on else None

    def __call__(self, name: str):
        if self._ann is None:
            return contextlib.nullcontext()
        return self._ann(name)


def drive(stack: Stack, mix: dict, rate_rps: Optional[float],
          seconds: float, seed: int, *,
          trace_dir: Optional[Path] = None,
          trace_span: tuple = (0.0, 0.0)) -> WindowLog:
    """Offer the mix's load for `seconds`, then wait up to DRAIN_LIMIT_S
    for what is still in flight. Open loop: each request is submitted when
    it is due (or as soon after as the loop gets to it). Closed loop: each
    client sends its next request as soon as it sees its last one end.
    With `trace_dir`, the device is profiled for `trace_span` =
    (offset, length) seconds of the window."""
    import jax
    sess = stack.sess
    ann = _Annotate(trace_dir is not None)
    reqs: Dict[int, ReqLog] = {}
    closed = mix["loop"] == "closed"
    if closed:
        pool = traffic.closed_loop_pool(mix, stack.questions, seed)
        owner: Dict[int, int] = {}
        nxt = [0]
    else:
        sched = traffic.open_loop(mix, rate_rps, seconds, stack.questions,
                                  seed)
    i = 0
    t_on = t_off = None
    traced = None

    def submit(r: traffic.Request, due: float) -> int:
        with ann("load generator"):
            rid = sess.submit(r.question, max_new=r.max_new)
        reqs[rid] = ReqLog(r.question, r.max_new, due, time.perf_counter())
        return rid

    def client_send(c: int, due: float) -> None:
        r = pool[nxt[0] % len(pool)]
        nxt[0] += 1
        owner[submit(r, due)] = c

    if closed:
        lead_in(sess, reqs, owner, client_send, int(mix["clients"]))
    start = time.perf_counter()
    end = start + seconds
    if trace_dir is not None:
        t_on, t_off = start + trace_span[0], start + sum(trace_span)
    while True:
        now = time.perf_counter()
        if t_on is not None and now >= t_on:
            jax.profiler.start_trace(str(trace_dir))
            traced = [time.perf_counter(), None]
            t_on = None
        if t_off is not None and traced and now >= t_off:
            traced[1] = time.perf_counter()
            jax.profiler.stop_trace()
            t_off = None
        if now >= end:
            break
        if not closed:
            while i < len(sched) and start + sched[i].due_s <= now:
                submit(sched[i], start + sched[i].due_s)
                i += 1
        if not sess.pending:
            wait = (start + sched[i].due_s if not closed and i < len(sched)
                    else end) - time.perf_counter()
            if wait > 0:
                time.sleep(min(wait, 0.002))
            continue
        with ann("session step"):
            events = sess.step()
        t = time.perf_counter()
        for ev in events:
            log = reqs.get(ev.req_id)
            if log is None:
                continue
            if ev.kind == "token":
                log.tokens.append(t)
            elif ev.kind in ("done", "shed", "failed"):
                log.end, log.state = t, ev.kind
                if closed and t < end:
                    client_send(owner[ev.req_id], t)
    if t_off is not None and traced:
        traced[1] = time.perf_counter()
        jax.profiler.stop_trace()
    limit = time.perf_counter() + DRAIN_LIMIT_S
    while sess.pending and time.perf_counter() < limit:
        events = sess.step()
        t = time.perf_counter()
        for ev in events:
            log = reqs.get(ev.req_id)
            if log is None:
                continue
            if ev.kind == "token":
                log.tokens.append(t)
            elif ev.kind in ("done", "shed", "failed"):
                log.end, log.state = t, ev.kind
    return WindowLog(start, end, reqs, tuple(traced) if traced else None)


def lead_in(sess, reqs, owner, client_send, clients: int) -> None:
    """Start a closed loop before its window opens: every client sends,
    and the loop runs until each has seen one answer end and sent its
    next request, so the window measures the loop in its steady state
    and not the burst of all clients starting at once."""
    now = time.perf_counter()
    for c in range(clients):
        client_send(c, now)
    first = set(owner)
    while first:
        events = sess.step()
        t = time.perf_counter()
        for ev in events:
            log = reqs.get(ev.req_id)
            if log is None:
                continue
            if ev.kind == "token":
                log.tokens.append(t)
            elif ev.kind in ("done", "shed", "failed"):
                log.end, log.state = t, ev.kind
                first.discard(ev.req_id)
                client_send(owner[ev.req_id], t)


def end_to_end(log: WindowLog) -> dict:
    """The four end-to-end metrics as the client saw them. TTFT runs from
    a request's due time to its first token, over the requests due in the
    window; one with no token by the window's end counts at its age then.
    ITL is every gap between consecutive tokens of a request, both
    received in the window. tokens_per_s counts the tokens received in
    the window."""
    ttft, itl, ntok = [], [], 0
    for r in log.reqs.values():
        got = [t for t in r.tokens if log.start <= t <= log.end]
        ntok += len(got)
        itl.extend(b - a for a, b in zip(got, got[1:]))
        if log.start <= r.due < log.end:
            first = r.tokens[0] if r.tokens else log.end
            ttft.append(min(first, log.end) - r.due)
    seconds = log.end - log.start
    return {
        "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
        "ttft_p95_ms": float(np.percentile(ttft, 95) * 1e3),
        "itl_p95_ms": float(np.percentile(itl, 95) * 1e3) if itl else None,
        "tokens_per_s": ntok / seconds,
    }


def outcome(log: WindowLog) -> dict:
    """Requests due in the window, and those shed, failed or never
    finished (after the drain) among them; plus how late the load
    generator submitted them."""
    due = [r for r in log.reqs.values() if log.start <= r.due < log.end]
    late = [r.submit - r.due for r in due]
    return {"attempted": len(due),
            "failed": sum(r.state != "done" for r in due),
            "generator_late_p95_ms": float(np.percentile(late, 95) * 1e3),
            "generator_late_max_ms": float(max(late) * 1e3)}


def free(stack: Stack) -> None:
    """Drop every reference to the program's device state, so the
    reference that runs next has the chip's memory to itself."""
    import jax
    stack.sess = stack.pipe = stack.slm = None
    gc.collect()
    jax.clear_caches()
    gc.collect()
