"""Table 5: Accuracy / TTFT / Power per (sLM x RAG method x dataset).

Accuracy = answer-in-final-context proxy (retrieval+SCR quality; no phone
sLM here). TTFT/Power combine measured retrieval/post-processing time with
the paper's Table-6 prompt-eval speeds and battery-impact coefficients.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.configs import get_reduced
from repro.data.synthetic import make_qa_corpus
from repro.serving.embedder import HashEmbedder
from repro.serving.rag import PIPELINES, SLM_SPEEDS, answer_in_context
from repro.serving.slm import SLM

STYLES = {"SQuAD-like": "squad", "HotpotQA-like": "hotpot",
          "TriviaQA-like": "trivia"}


def run(mode="quick"):
    nq = 20 if mode == "quick" else 80
    # Real-generation TTFT reference: Engine prefill + first token on the
    # reduced on-device sLM (one shared instance -> one compile), reported
    # beside the analytical Table-6 ttft estimate on every row.
    slm_real = SLM(get_reduced("qwen25_0_5b"))
    slm_real.warmup()
    # measured once per (style, pipeline): the real engine/prompts are
    # identical for every Table-6 slm row, only the analytical column
    # differs, so re-measuring per slm would triple the Engine waves
    real_ttft_cache = {}
    for label, style in STYLES.items():
        corpus = make_qa_corpus(style, n_docs=150, n_questions=nq, seed=0)
        emb = HashEmbedder(dim=128).fit(corpus.docs)
        for slm in SLM_SPEEDS:
            for pname, cls in PIPELINES.items():
                pipe = cls(corpus.docs, emb, top_k=3, slm=slm)
                # Table-5 rows: host retrieval for EVERY pipeline so the
                # per-query TTFT/power/accuracy comparison stays
                # apples-to-apples (the interpret-mode Pallas path on
                # non-TPU hosts is correctness-grade, not timing-grade)
                pipe.device_retrieval = False
                questions = [e.question for e in corpus.examples[:nq]]
                answers = [pipe.answer(q) for q in questions]
                # answer-in-final-context accuracy from the same answers
                # (no second per-query pass)
                acc = float(np.mean(
                    [answer_in_context(ex, a)
                     for ex, a in zip(corpus.examples[:nq], answers)]))
                ttft = np.mean([a.ttft_model_s for a in answers])
                power = np.mean([a.energy_model_j for a in answers])
                tok = np.mean([a.prompt_tokens for a in answers])
                # measured LM-side TTFT on this pipeline's actual prompts
                if (label, pname) not in real_ttft_cache:
                    n_real = min(3, len(answers))
                    real_ttft_cache[label, pname] = float(np.mean(
                        [slm_real.measure_ttft(a.prompt)
                         for a in answers[:n_real]]))
                real_ttft = real_ttft_cache[label, pname]
                emit(f"rag.{slm}.{label}.{pname}", ttft * 1e6,
                     f"acc={acc:.2f};ttft_s={ttft:.2f};"
                     f"real_ttft_s={real_ttft:.3f};"
                     f"real_arch={slm_real.arch}-reduced;"
                     f"power_J={power:.2f};tokens={tok:.0f}")
                # batched-serving throughput for pipelines with batched
                # retrieval (one embed + one fused device retrieval)
                if pipe._finish is not None:
                    pipe.device_retrieval = cls.device_retrieval
                    retrieval_mode = ("device"
                                      if pipe._use_device_retrieval()
                                      else "host")
                    if retrieval_mode == "device":
                        # warm the fused route->scan jit at batch shape
                        # B=nq (jit caches key on B) to exclude compile
                        pipe._retrieve_batch(pipe.doc_vecs[:nq], pipe.top_k)
                    t0 = time.perf_counter()
                    batch = pipe.answer_batch(questions)
                    wall = time.perf_counter() - t0
                    bttft = np.mean([a.ttft_model_s for a in batch])
                    emit(f"rag_batched.{slm}.{label}.{pname}",
                         wall / nq * 1e6,
                         f"amortized_ttft_s={bttft:.2f};"
                         f"batch_wall_s={wall:.2f};B={nq};"
                         f"retrieval={retrieval_mode}")


if __name__ == "__main__":
    run()
