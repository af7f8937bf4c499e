"""The four RAG pipelines the paper compares (Figure 1, Table 5):

  Naive-RAG    : vector search -> full docs -> sLM.
  Advanced-RAG : vector search (wider) -> re-ranker -> full docs -> sLM.
  EdgeRAG      : IVF-DISK index + embedding cache -> full docs -> sLM.
  MobileRAG    : EcoVector -> SCR (condense + reorder) -> sLM.

Each `answer()` returns the final prompt, timing breakdown, token counts,
and the paper-model TTFT/energy estimates (Table 6 speeds; §3.4.3 power),
so Table-5-style comparisons run offline without a phone.
"""
from __future__ import annotations

import os
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable, List, Optional, Sequence

import numpy as np

from repro.core.analytical import HW, energy_mj
from repro.core.baselines import IVFDisk
from repro.core.ecovector import EcoVector
from repro.core.scr import (SCRConfig, SCRResult, apply_scr, apply_scr_batch,
                            build_prompt)
from repro.core.window_index import WindowIndex
from repro.serving.trace import NO_SPAN

if TYPE_CHECKING:
    from repro.config import ModelConfig

# Table 6: measured on Galaxy S24
SLM_SPEEDS = {
    "qwen25_0_5b": {"prompt_tps": 90.0, "gen_tps": 14.5, "batt_pct_1k": 0.10},
    "qwen25_1_5b": {"prompt_tps": 50.0, "gen_tps": 10.0, "batt_pct_1k": 0.30},
    "deepseek_r1_1_5b": {"prompt_tps": 35.0, "gen_tps": 9.0,
                         "batt_pct_1k": 0.36},
}
BATTERY_J = 4000e-3 * 3600 * 3.8  # 4000 mAh at 3.8 V -> ~54.7 kJ


@dataclass
class RAGAnswer:
    prompt: str
    doc_ids: List[int]
    retrieval_s: float
    post_s: float                   # re-rank / SCR time
    prompt_tokens: int
    ttft_model_s: float             # retrieval + post + prompt eval (model)
    energy_model_j: float
    scr: Optional[SCRResult] = None
    generated: Optional[str] = None
    # real-generation fields, filled by answer(..., generate=True): token
    # ids decoded by serving.Engine on the reduced on-device sLM, and the
    # MEASURED prefill+first-token time (vs the Table-6 ttft_model_s model)
    gen_tokens: Optional[List[int]] = None
    ttft_measured_s: Optional[float] = None


def _tok_count(text: str) -> int:
    return len(text.split())


class RAGBase:
    name = "base"
    # Retrieval through the index's fused batched device path
    # (EcoVector.search_device_batched) when available. False = host
    # search; True = always device; None = auto (device on TPU only — the
    # interpret-mode Pallas path on other backends is correctness-grade,
    # not a serving fast path). MobileRAG defaults to auto.
    device_retrieval: Optional[bool] = False

    def __init__(self, docs: Sequence[str], embed: Callable, *,
                 top_k: int = 3, slm: str = "qwen25_0_5b", index=None,
                 generator: Optional[Callable] = None,
                 device_retrieval: Optional[bool] = None,
                 gen_cfg: Optional["ModelConfig"] = None,
                 device_budget_bytes: Optional[float] = None,
                 _skip_corpus_embed: bool = False):
        self.docs = list(docs)
        self.embed = embed
        self.top_k = top_k
        # IVF probe width for every retrieval; the SLO controller's
        # degrade ladder (serving/session.py) lowers it under deadline
        # pressure and restores it after the chunk
        self.n_probe = 4
        # device-memory budget for the retrieval index (DESIGN.md §14):
        # None = all-resident; an int is bytes; a float in (0, 1] is a
        # fraction of the all-resident pack. Builds a TieredEcoVector.
        self.device_budget_bytes = device_budget_bytes
        self.slm = SLM_SPEEDS[slm]
        self.generator = generator
        # degradation-ladder state: on an index-search exception the
        # pipeline answers from the last good retrieval (or the corpus
        # head) instead of raising — counted, never silent
        self.retrieval_fallbacks = 0
        self._last_good_ids: Optional[List[List[int]]] = None
        # generator config for answer(..., generate=True); the Table-6
        # `slm` keys are speed models only. None is qwen25_0_5b at the
        # reduced CPU smoke size; pass get_config("qwen25_0_5b") to
        # generate at its published widths
        self.gen_cfg = gen_cfg
        self._slm_engine = None
        # the TraceSink of the RagSession serving this pipeline, which
        # records its stages as comp="rag" spans (None = untraced)
        self.trace = None
        if device_retrieval is not None:
            self.device_retrieval = device_retrieval
        if hasattr(embed, "fit") and not getattr(embed, "fitted", True):
            embed.fit(self.docs)
        t0 = time.perf_counter()
        # a pipeline restored from a durable snapshot skips the corpus
        # embed entirely — the whole point of persisting retrieval state
        self.doc_vecs = (None if (_skip_corpus_embed and index is not None)
                         else np.asarray(embed(self.docs), np.float32))
        self.index = index or self._build_index()
        if (self.device_budget_bytes is not None
                and hasattr(self.index, "set_device_budget")):
            self.index.set_device_budget(
                self._resolve_device_budget(self.index))
        self.build_s = time.perf_counter() - t0

    def _span(self, name: str, **attrs):
        """A `rag/<name>` span; a shared no-op context untraced."""
        if self.trace is None:
            return NO_SPAN
        return self.trace.span("rag", name, **attrs)

    def _resolve_device_budget(self, index) -> int:
        b = self.device_budget_bytes
        if 0 < b <= 1.0:             # fraction of the all-resident pack
            return int(b * index.all_resident_bytes())
        return int(b)

    def _build_index(self):
        n_clusters = max(4, len(self.docs) // 64)
        if self.device_budget_bytes is not None:
            from repro.core.tiered import TieredEcoVector
            return TieredEcoVector(
                self.doc_vecs.shape[1],
                n_clusters=n_clusters).build(self.doc_vecs)
        ev = EcoVector(self.doc_vecs.shape[1], n_clusters=n_clusters)
        return ev.build(self.doc_vecs)

    def _use_device_retrieval(self) -> bool:
        if self.device_retrieval is None:
            import jax
            return jax.default_backend() == "tpu"
        return self.device_retrieval

    def _retrieve_batch(self, qvs: np.ndarray, k: int) -> List[List[int]]:
        """Retrieve for a [B, d] batch of query vectors in one call when
        the index has a batched device path, else per-query host search.
        An index exception degrades instead of failing the request: the
        last good retrieval's ids (or the corpus head) are reused and
        `retrieval_fallbacks` counts the decision."""
        qvs = np.atleast_2d(np.asarray(qvs, np.float32))
        try:
            if self._use_device_retrieval() and hasattr(
                    self.index, "search_device_batched"):
                ids_b, _ = self.index.search_device_batched(
                    qvs, k=k, n_probe=self.n_probe)
            else:
                ids_b = [self.index.search(qv, k=k, n_probe=self.n_probe)[0]
                         for qv in qvs]
        except Exception:
            self.retrieval_fallbacks += 1
            return self._fallback_ids(len(qvs), k)
        clean = [[int(i) for i in row if 0 <= int(i) < len(self.docs)]
                 for row in ids_b]
        self._last_good_ids = clean
        return clean

    def _fallback_ids(self, n: int, k: int) -> List[List[int]]:
        """Stale-but-serviceable doc ids when the index is down: cycle
        the last successful batch's rows, else the first k documents."""
        if self._last_good_ids:
            rows = self._last_good_ids
            return [list(rows[i % len(rows)]) for i in range(n)]
        return [list(range(min(k, len(self.docs)))) for _ in range(n)]

    def _retrieve(self, qv, k):
        return self._retrieve_batch(qv[None], k)[0]

    def _make_prompt(self, query: str, docs: List[str],
                     order: List[int]) -> str:
        ctx = "\n\n".join(f"[Doc {order[i] + 1}] {d}"
                          for i, d in enumerate(docs))
        return f"Context:\n{ctx}\n\nQuestion: {query}\nAnswer:"

    def _finalize(self, query, prompt, doc_ids, t_ret, t_post,
                  scr=None) -> RAGAnswer:
        ptok = _tok_count(prompt)
        t_eval = ptok / self.slm["prompt_tps"]
        ttft = t_ret + t_post + t_eval
        # energy: retrieval+post as CPU time (paper §3.4.3) + LM cost from
        # the battery-impact table
        e_cpu = energy_mj((t_ret + t_post) * 1e3, 0.0) * 1e-3
        e_lm = ptok / 1000.0 * self.slm["batt_pct_1k"] / 100.0 * BATTERY_J
        gen = None
        if self.generator is not None:
            gen = self.generator(prompt)
        return RAGAnswer(prompt, doc_ids, t_ret, t_post, ptok, ttft,
                         e_cpu + e_lm, scr, gen)

    # Pipelines with simple retrieve->post flows set `_finish(query, ids,
    # t_ret, qv=...)` and inherit the shared answer/answer_batch templates
    # below (`qv` is the already-embedded query vector, so post stages
    # never pay a second embedder forward).
    _finish = None

    # --------------------------------------------- real on-device decoding

    def _ensure_slm(self):
        if self._slm_engine is None:
            from repro.configs import get_reduced
            from repro.serving.slm import SLM
            self._slm_engine = SLM(self.gen_cfg
                                   or get_reduced("qwen25_0_5b"))
        return self._slm_engine

    def _attach_generation(self, answers: List[RAGAnswer],
                           max_new: int = 16) -> List[RAGAnswer]:
        """Run the final prompts through the real Engine decode loop (one
        fixed-shape wave for the whole list) and record the decoded token
        ids + measured prefill TTFT on each answer."""
        slm = self._ensure_slm()
        gens = slm.generate([a.prompt for a in answers], max_new=max_new)
        for a, g in zip(answers, gens):
            a.gen_tokens = g.tokens
            a.generated = g.text
            a.ttft_measured_s = g.ttft_s
        return answers

    def answer(self, query: str, *, generate: bool = False,
               max_new: int = 16) -> RAGAnswer:
        """One query end to end. With `generate=True` the answer carries
        REAL decoded tokens from serving.Engine (retrieval -> post -> LM
        generate on device), not just the analytical TTFT estimate."""
        if self._finish is None:
            raise NotImplementedError
        t0 = time.perf_counter()
        qv = np.asarray(self.embed([query]))[0]
        ids = self._retrieve(qv, self.top_k)
        t_ret = time.perf_counter() - t0
        ans = self._finish(query, ids, t_ret, qv=qv)
        if generate:
            self._attach_generation([ans], max_new=max_new)
        return ans

    def answer_batch(self, queries: Sequence[str], *,
                     generate: bool = False,
                     max_new: int = 16) -> List[RAGAnswer]:
        """Batched serving entry point: one embed + one (device-)batched
        retrieval for the whole query set, then per-query post-processing.
        Pipelines without a `_finish` hook fall back to per-query answers.
        `generate=True` routes through a RagSession over the continuous
        engine: retrieval/SCR for the next chunk of queries overlaps
        decode of the previous ones (DESIGN.md §9)."""
        queries = list(queries)
        if generate and queries:
            return self._answer_batch_generate(queries, max_new)
        if self._finish is None:
            return [self.answer(q) for q in queries]
        t0 = time.perf_counter()
        qvs = np.asarray(self.embed(queries), np.float32)
        ids_b = self._retrieve_batch(qvs, self.top_k)
        t_ret = (time.perf_counter() - t0) / max(len(queries), 1)
        return [self._finish(q, ids, t_ret, qv=qv)
                for q, ids, qv in zip(queries, ids_b, qvs)]

    # -------------------------------------------- request-centric serving

    def session(self, *, max_new: int = 16, slots: int = 4,
                retrieve_chunk: int = 4, greedy: bool = True,
                seed: int = 0, max_pending: Optional[int] = None,
                deadline_s: Optional[float] = None,
                trace=None, slo_s: Optional[float] = None):
        """A RagSession over this pipeline: submit/step/stream with
        continuous-batching decode (raises ValueError when `gen_cfg`
        has no slot-paged KV path). `greedy=False` samples each request
        from its own co-residency-independent PRNG stream. `max_pending`
        bounds session admission (degrade past half, shed at the bound);
        `deadline_s` is the default per-request deadline. `trace` is a
        shared TraceSink (docs/OBSERVABILITY.md); `slo_s` turns on
        SLO-aware admission planned from the live trace window."""
        from repro.serving.session import RagSession
        return RagSession(self, max_new=max_new, slots=slots,
                          retrieve_chunk=retrieve_chunk, greedy=greedy,
                          seed=seed, max_pending=max_pending,
                          deadline_s=deadline_s, trace=trace, slo_s=slo_s)

    def stream(self, queries: Sequence[str] = (), *, max_new: int = 16,
               slots: int = 4, retrieve_chunk: int = 4):
        """Event generator (submitted/retrieved/condensed/token/done) for
        a batch of queries through a fresh RagSession."""
        return self.session(max_new=max_new, slots=slots,
                            retrieve_chunk=retrieve_chunk).stream(queries)

    def _answer_batch_generate(self, queries: List[str],
                               max_new: int) -> List[RAGAnswer]:
        """generate=True body: a RagSession pipelines retrieval/SCR chunks
        into the continuous decode loop. Falls back to condense-everything
        + one legacy Engine wave for archs without paged KV support."""
        try:
            sess = self.session(max_new=max_new)
        except ValueError:
            out = self.answer_batch(queries, generate=False)
            return self._attach_generation(out, max_new=max_new)
        return sess.run(queries)


class NaiveRAG(RAGBase):
    name = "Naive-RAG"

    def _finish(self, query: str, ids: List[int], t_ret: float,
                qv=None) -> RAGAnswer:
        prompt = self._make_prompt(query, [self.docs[i] for i in ids], ids)
        return self._finalize(query, prompt, ids, t_ret, 0.0)


class AdvancedRAG(RAGBase):
    """Re-Ranker: re-scores a wider candidate set with a second pass
    (max sentence similarity — the lightweight stand-in for the re-rank
    model, which adds the post-retrieval latency the paper measures)."""
    name = "Advanced-RAG"

    def answer(self, query: str, *, generate: bool = False,
               max_new: int = 16) -> RAGAnswer:
        t0 = time.perf_counter()
        qv = np.asarray(self.embed([query]))[0]
        ids = self._retrieve(qv, self.top_k * 3)
        t_ret = time.perf_counter() - t0
        t1 = time.perf_counter()
        from repro.core.scr import split_sentences
        scores = []
        for i in ids:
            sents = split_sentences(self.docs[i]) or [self.docs[i]]
            sv = np.asarray(self.embed(sents))
            scores.append(float(np.max(sv @ qv)))
        order = np.argsort(scores)[::-1][: self.top_k]
        ids = [ids[i] for i in order]
        t_post = time.perf_counter() - t1
        prompt = self._make_prompt(query, [self.docs[i] for i in ids], ids)
        ans = self._finalize(query, prompt, ids, t_ret, t_post)
        if generate:
            self._attach_generation([ans], max_new=max_new)
        return ans


class EdgeRAG(RAGBase):
    """IVF-DISK retrieval + embedding cache (the paper's EdgeRAG baseline).

    The query-embedding cache is a bounded LRU (`qcache_cap` entries) so a
    long-running query stream cannot grow it without limit; hit/miss
    counters feed the serving benchmarks."""
    name = "EdgeRAG"
    qcache_cap = 256

    def _build_index(self):
        idx = IVFDisk(self.doc_vecs.shape[1],
                      n_clusters=max(4, len(self.docs) // 64))
        idx.build(self.doc_vecs)
        self._qcache: "OrderedDict[str, np.ndarray]" = OrderedDict()
        self.qcache_hits = 0
        self.qcache_misses = 0
        return idx

    def _embed_query_cached(self, query: str) -> np.ndarray:
        qv = self._qcache.get(query)
        if qv is not None:
            self._qcache.move_to_end(query)     # LRU promotion
            self.qcache_hits += 1
            return qv
        qv = np.asarray(self.embed([query]))[0]
        self.qcache_misses += 1
        self._qcache[query] = qv
        while len(self._qcache) > self.qcache_cap:
            self._qcache.popitem(last=False)    # evict LRU head
        return qv

    def answer(self, query: str, *, generate: bool = False,
               max_new: int = 16) -> RAGAnswer:
        t0 = time.perf_counter()
        qv = self._embed_query_cached(query)
        ids = self._retrieve(qv, self.top_k)
        t_ret = time.perf_counter() - t0
        prompt = self._make_prompt(query, [self.docs[i] for i in ids], ids)
        ans = self._finalize(query, prompt, ids, t_ret, 0.0)
        if generate:
            self._attach_generation([ans], max_new=max_new)
        return ans


class MobileRAG(RAGBase):
    """EcoVector + SCR (the paper's method). Retrieval runs on the fused
    batched EcoVector device path (route + scan in one jitted call); SCR
    runs against the corpus-resident window index (every document's
    windows split/embedded once at construction, DESIGN.md §6) with the
    fused `scr_select` kernel picking best windows on device —
    per-query post-retrieval work is one query embed, one kernel call,
    and host string assembly. `use_window_index=False` keeps the legacy
    re-embed-every-window-per-query path for before/after benchmarks."""
    name = "MobileRAG"
    device_retrieval = None          # auto: fused device path on TPU

    def __init__(self, docs: Sequence[str], embed: Callable, *,
                 scr: SCRConfig = SCRConfig(),
                 use_window_index: bool = True,
                 retrieval_state: Optional[str] = None, **kw):
        """`retrieval_state` points at a durable snapshot directory
        (DESIGN.md §12): when it holds a committed generation, EcoVector
        and the window index are restored from disk (WAL replayed, zero
        re-embedding); otherwise the pipeline builds normally and commits
        its first generation there. Subsequent index mutations are
        journaled; `save_retrieval()` compacts them into a new
        generation."""
        self.retrieval_state = retrieval_state
        loaded_index = None
        loaded_wi = None
        if retrieval_state is not None:
            loader = EcoVector.load
            if kw.get("device_budget_bytes") is not None:
                # budgeted pipeline: restore the tiered index so tier
                # assignment and the cold pack come back from the snapshot
                from repro.core.tiered import TieredEcoVector
                loader = TieredEcoVector.load
            loaded_index = self._load_state_part(
                loader, os.path.join(retrieval_state, "ecovector"))
            if use_window_index:
                loaded_wi = self._load_state_part(
                    lambda root: WindowIndex.load(embed, root),
                    os.path.join(retrieval_state, "windows"))
        if loaded_index is not None:
            super().__init__(docs, embed, index=loaded_index,
                             _skip_corpus_embed=True, **kw)
        else:
            super().__init__(docs, embed, **kw)
        self.scr_cfg = scr
        self.window_index = loaded_wi
        self.scr_build_s = 0.0
        self.scr_fallbacks = 0       # SCR stage raised -> full-doc prompt
        if use_window_index and self.window_index is None:
            t0 = time.perf_counter()
            self.window_index = WindowIndex(self.embed, scr).build(self.docs)
            self.scr_build_s = time.perf_counter() - t0
        if self.window_index is not None:
            self._sync_window_index()   # docs beyond the snapshot
        if retrieval_state is not None and (loaded_index is None
                                            or loaded_wi is None):
            self.save_retrieval()       # establish / complete the snapshot

    @staticmethod
    def _load_state_part(loader, root: str):
        """One component's restore: absent state means build-from-scratch
        (first run); corrupt state is a loud warning, then rebuild — a
        rotten snapshot must never brick pipeline construction."""
        from repro.core import store as _store
        try:
            return loader(root)
        except FileNotFoundError:
            return None
        except (_store.StoreError, OSError) as e:
            import warnings
            warnings.warn(f"retrieval state under {root} failed "
                          f"validation ({e}); rebuilding from source",
                          stacklevel=3)
            return None

    def save_retrieval(self, root: Optional[str] = None) -> None:
        """Commit the current retrieval state (EcoVector generation +
        window-index generation) under `root`/`retrieval_state`, folding
        any journaled mutations into the new snapshots."""
        root = root or self.retrieval_state
        if root is None:
            raise ValueError("no retrieval_state directory configured")
        self.retrieval_state = root
        if hasattr(self.index, "save"):
            self.index.save(os.path.join(root, "ecovector"))
        if self.window_index is not None:
            self.window_index.save(os.path.join(root, "windows"))

    def _sync_window_index(self):
        """Pick up documents appended to `self.docs` since the index was
        built (the retrieval-index update path): each new doc is one
        incremental `add` — only its block gets embedded and packed."""
        w = self.window_index
        while len(w) < len(self.docs):
            w.add(self.docs[len(w)])

    def _finish(self, query: str, ids: List[int], t_ret: float,
                qv=None) -> RAGAnswer:
        t1 = time.perf_counter()
        res = None
        try:
            if self.window_index is not None:
                self._sync_window_index()
                qvs = (None if qv is None
                       else np.asarray(qv, np.float32)[None])
                res = apply_scr_batch([query], [ids], self.window_index,
                                      self.embed, qvs=qvs)[0]
            else:
                res = apply_scr(query, [self.docs[i] for i in ids],
                                self.embed, self.scr_cfg)
        except Exception:
            # degradation ladder: SCR down -> serve the full retrieved
            # docs (NaiveRAG-shaped prompt) rather than fail the request
            self.scr_fallbacks += 1
        t_post = time.perf_counter() - t1
        if res is None:
            prompt = self._make_prompt(query, [self.docs[i] for i in ids],
                                       ids)
            return self._finalize(query, prompt, ids, t_ret, t_post)
        prompt = build_prompt(query, res)
        ids = [ids[i] for i in res.order]
        return self._finalize(query, prompt, ids, t_ret, t_post, scr=res)

    def answer_batch(self, queries: Sequence[str], *,
                     generate: bool = False,
                     max_new: int = 16) -> List[RAGAnswer]:
        """Fully batched MobileRAG: ONE query embed feeds both the fused
        EcoVector retrieval and the fused SCR select; everything after the
        two device calls is host-side string assembly. Traced, the stages
        are `rag/embed`, `rag/search` (retrieval and its readback),
        `rag/scr` and `rag/prompt` spans, each with the batch size `n`.
        `generate=True`
        routes through the RagSession (whose retrieval chunks re-enter
        this fused path with generate=False) so SCR for the next chunk
        overlaps continuous decode of the previous one."""
        queries = list(queries)
        if self.window_index is None or not queries:
            return super().answer_batch(queries, generate=generate,
                                        max_new=max_new)
        if generate:
            return self._answer_batch_generate(queries, max_new)
        self._sync_window_index()
        n = len(queries)
        t0 = time.perf_counter()
        with self._span("embed", n=n):
            qvs = np.asarray(self.embed(queries), np.float32)
        with self._span("search", n=n):
            ids_b = self._retrieve_batch(qvs, self.top_k)
        t_ret = (time.perf_counter() - t0) / n
        t1 = time.perf_counter()
        try:
            with self._span("scr", n=n):
                results = apply_scr_batch(queries, ids_b, self.window_index,
                                          self.embed, qvs=qvs)
        except Exception:
            # SCR stage down for the whole batch: degrade every query to
            # its full retrieved docs instead of raising
            self.scr_fallbacks += 1
            t_post = (time.perf_counter() - t1) / n
            return [self._finalize(
                        q, self._make_prompt(q, [self.docs[i] for i in ids],
                                             ids), ids, t_ret, t_post)
                    for q, ids in zip(queries, ids_b)]
        t_post = (time.perf_counter() - t1) / n
        out = []
        with self._span("prompt", n=n):
            for q, ids, res in zip(queries, ids_b, results):
                prompt = build_prompt(q, res)
                out.append(self._finalize(q, prompt,
                                          [ids[i] for i in res.order],
                                          t_ret, t_post, scr=res))
        return out


PIPELINES = {
    "naive": NaiveRAG,
    "advanced": AdvancedRAG,
    "edge": EdgeRAG,
    "mobile": MobileRAG,
}


def answer_in_context(example, ans: RAGAnswer) -> bool:
    """The planted answer sentence survived retrieval *and* (for
    MobileRAG) SCR condensation — the single accuracy predicate shared by
    every Table-5 consumer."""
    return example.answer.lower() in ans.prompt.lower()


def accuracy(pipe: RAGBase, examples, max_q: Optional[int] = None) -> float:
    """Answer-in-final-context accuracy: the retrieval-quality proxy for
    Table 5 accuracy (no on-device sLM here). Runs through `answer_batch`
    so Table-5 accuracy uses the fused batched retrieval/SCR path (one
    embed + one device retrieval + one SCR select for the whole set)."""
    exs = list(examples[:max_q])
    if not exs:
        return 0.0
    answers = pipe.answer_batch([ex.question for ex in exs])
    ok = sum(bool(answer_in_context(ex, a)) for ex, a in zip(exs, answers))
    return ok / len(exs)
