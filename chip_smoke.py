#!/usr/bin/env python3
"""Run MobileRAG's served path once on one TPU chip and check what it serves.

    python chip_smoke.py [--seed 0]

Everything runs in this one process, because a chip belongs to one
process at a time:

1. build: a seeded SQuAD-shaped corpus, a `HashEmbedder`, and `MobileRAG`
   with device retrieval on. The generator is `qwen25_0_5b` at its
   published widths with random weights made from the seed.
2. serve: a `RagSession` driven the way `launch/serve.py --stream` drives
   it (8 questions, `max_new=16`, 4 slots, greedy), then 8 more on the
   warm executables.
3. check: every request done; every fallback, retry, shed, degrade and
   failure counter at zero; retrieval and SCR equal to `kernels/ref.py`
   on the same device arrays; generated tokens in the vocabulary and
   prefill logits finite.

It exits nonzero, and prints no ok line, when JAX finds no TPU or any
check fails. The last line of a passing run is one JSON object naming
the device. The compile cache lives where `repro.launch.compile_cache`
puts it; a second run reports the hits.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data.synthetic import make_qa_corpus  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model  # noqa: E402
from repro.serving.embedder import HashEmbedder  # noqa: E402
from repro.serving.rag import MobileRAG  # noqa: E402

DOCS = 2000            # corpus size: 31 EcoVector clusters of about 64
QUESTIONS = 8          # per serving pass; two passes, cold then warm
MAX_NEW = 16
SLOTS = 4
SCR_TOL = 1e-4         # |kernel - reference| on query-window scores
TIE_TOL = 1e-5         # float64 distances or scores closer than this tie


def require_tpu():
    """The first device, which must be a TPU: a smoke run on anything
    else would prove nothing about the chip."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    return dev


def build(seed: int, n_docs: int, gen_cfg):
    """Corpus, embedder and MobileRAG with device retrieval on."""
    corpus = make_qa_corpus("squad", n_docs=n_docs,
                            n_questions=2 * QUESTIONS, seed=seed)
    emb = HashEmbedder(dim=128, seed=seed)
    pipe = MobileRAG(corpus.docs, emb, top_k=3, device_retrieval=True,
                     gen_cfg=gen_cfg)
    return corpus, pipe


def serve(sess, questions):
    """Submit `questions` and step the session until it drains; returns
    their request ids."""
    rids = [sess.submit(q) for q in questions]
    while sess.pending:
        sess.step()
    return rids


def check_session(pipe, sess, rids, vocab: int) -> list:
    """Every request done with in-vocabulary tokens, and no counter of a
    fallback, retry, shed, degrade or failure above zero."""
    bad = []
    for rid in rids:
        req = sess.requests[rid]
        if req.state != "done":
            bad.append(f"request {rid} ended {req.state!r}, not done")
            continue
        toks = req.answer.gen_tokens
        if not toks or not all(0 <= t < vocab for t in toks):
            bad.append(f"request {rid} tokens out of [0, {vocab}): {toks}")
    c = sess.counters
    counters = {"retrieval_fallbacks": pipe.retrieval_fallbacks,
                "scr_fallbacks": pipe.scr_fallbacks,
                "retrieval_retries": c.retrieval_retries,
                "failed": c.failed, "shed_deadline": c.shed_deadline,
                "shed_overload": c.shed_overload,
                "shed_oversize": c.shed_oversize, "shed_slo": c.shed_slo,
                "degraded": c.degraded, "degraded_slo": c.degraded_slo}
    bad += [f"{k}={v}" for k, v in counters.items() if v]
    return bad


def check_retrieval(pipe, qvs, answers) -> tuple:
    """Fused route->scan kernel vs `ref.route_and_scan` on the index's
    device arrays, and the session's served doc ids vs the reference's.
    Ids must match except between documents whose float64 distances to
    the query tie within TIE_TOL: the kernel's ||x||^2 - 2x.q + ||q||^2
    and the reference's sum of squared differences round a unit or two
    apart in float32, which can order a near-tie either way. Returns
    (failures, reference doc ids per k, ties passed)."""
    bad, ties = [], 0
    data_j, lens_j, cent_j = pipe.index._device_arrays()
    slot_ids = pipe.index.device_pack()[2].reshape(-1)
    vecs = np.asarray(pipe.doc_vecs, np.float64)
    q64 = np.asarray(qvs, np.float64)

    def same(b, got, want):
        nonlocal ties
        for g, w in zip(got, want):
            if g == w:
                continue
            if min(g, w) < 0 or abs(((vecs[g] - q64[b]) ** 2).sum()
                                    - ((vecs[w] - q64[b]) ** 2).sum()
                                    ) > TIE_TOL:
                return False
            ties += 1
        return len(got) == len(want)

    q = jnp.asarray(qvs)
    ref_fn = jax.jit(ref.route_and_scan, static_argnames=("n_probe", "k"))
    ref_ids = {}
    for k in (pipe.top_k, 10):
        dk, sk, pk = ops.route_and_scan(q, cent_j, data_j, lens_j,
                                        n_probe=pipe.n_probe, k=k)
        dr, sr, pr = ref_fn(q, cent_j, data_j, lens_j, n_probe=pipe.n_probe,
                            k=k)
        dk, dr = np.asarray(dk), np.asarray(dr)
        kid, rid = (np.where(s >= 0, slot_ids[np.clip(s, 0, None)], -1)
                    for s in (np.asarray(sk), np.asarray(sr)))
        if not (np.asarray(pk) == np.asarray(pr)).all():
            bad.append(f"k={k}: routed probes differ from the reference")
        for b in range(len(qvs)):
            if not same(b, kid[b], rid[b]):
                bad.append(f"k={k}: query {b}: scanned ids {kid[b]} != "
                           f"reference {rid[b]}")
        if not np.allclose(dk, dr, rtol=1e-5, atol=1e-4):
            bad.append(f"k={k}: scanned distances differ from the "
                       f"reference by {np.abs(dk - dr).max():.3g}")
        ref_ids[k] = rid
    for b, ans in enumerate(answers):
        if ans is None:                 # not served; check_session reports
            continue
        by_dist = lambda i: ((vecs[i] - q64[b]) ** 2).sum()  # noqa: E731
        got = sorted(ans.doc_ids, key=by_dist)
        want = sorted((int(i) for i in ref_ids[pipe.top_k][b] if i >= 0),
                      key=by_dist)
        if not same(b, got, want):
            bad.append(f"query {b}: served docs {got} != reference {want}")
    return bad, ref_ids, ties


def check_scr(pipe, qvs, ref_ids) -> list:
    """`scr_select` vs `ref.scr_select` on the window pack's device arrays,
    at the served K and at K=10 (two doc tiles). The reference runs its
    einsum at full f32 precision, as the kernel does. Window ids must
    match except between windows whose float64 scores tie: the corpus
    repeats sentences, so a document can hold identical windows, and the
    two float32 sums may round them a unit apart in either order."""
    bad = []
    data_j, lens_j = pipe.window_index.device_arrays()
    q = jnp.asarray(qvs)
    ref_fn = jax.jit(ref.scr_select)
    for k, ids in ref_ids.items():
        sk, wk = ops.scr_select(q, data_j, lens_j, jnp.asarray(ids, jnp.int32))
        with jax.default_matmul_precision("float32"):
            sr, wr = ref_fn(q, data_j, lens_j, jnp.asarray(ids, jnp.int32))
        err = float(np.abs(np.asarray(sk) - np.asarray(sr)).max())
        if err > SCR_TOL:
            bad.append(f"K={k}: scr_select scores off the reference by "
                       f"{err:.3g} > {SCR_TOL}")
        wk, wr = np.asarray(wk), np.asarray(wr)
        for b, j in np.argwhere(wk != wr):
            a, r = int(wk[b, j]), int(wr[b, j])
            row = np.asarray(data_j[int(ids[b, j])], np.float64)
            qb = qvs[b].astype(np.float64)
            if min(a, r) < 0 or abs(row[a] @ qb - row[r] @ qb) > TIE_TOL:
                bad.append(f"K={k}: query {b} doc {ids[b, j]}: window {a} "
                           f"!= reference window {r}")
    return bad


def check_logits(pipe, sess, answers) -> tuple:
    """Prefill logits of one served prompt are finite. Returns
    (failures, whether their argmax equals the served first token)."""
    ans = next((a for a in answers if a is not None and a.gen_tokens), None)
    if ans is None:
        return ["no served answer to check logits on"], False
    toks = pipe._ensure_slm().encode_prompt(ans.prompt, bucket=False)
    prefill = jax.jit(functools.partial(model.prefill, sess.engine.cfg))
    logits, _ = prefill(sess.engine.params,
                        {"tokens": jnp.asarray(toks[None])})
    logits = np.asarray(logits, np.float32)
    bad = [] if np.isfinite(logits).all() else ["prefill logits non-finite"]
    return bad, int(np.argmax(logits[0])) == ans.gen_tokens[0]


def run_smoke(seed: int, n_docs: int, gen_cfg, log=print) -> list:
    """All phases on the current default device; returns the failures
    (empty when every check passed)."""
    t0 = time.perf_counter()
    corpus, pipe = build(seed, n_docs, gen_cfg)
    t_build = time.perf_counter() - t0
    idx, wi = pipe.index, pipe.window_index
    data, _, _, cap = idx.device_pack()
    log(f"[model] {gen_cfg.name}: layers={gen_cfg.num_layers} "
        f"d_model={gen_cfg.d_model} heads={gen_cfg.num_heads}/"
        f"{gen_cfg.num_kv_heads} d_ff={gen_cfg.d_ff} "
        f"vocab={gen_cfg.vocab_size} params={gen_cfg.param_count()} "
        f"dtype={gen_cfg.dtype}")
    log(f"[corpus] docs={len(corpus.docs)} index clusters={idx.n_clusters} "
        f"cap={cap} dim={data.shape[2]} pack={list(data.shape)} "
        f"windows pack={list(wi.device_arrays()[0].shape)} "
        f"build_s={t_build:.3f}")

    t0 = time.perf_counter()
    sess = pipe.session(max_new=MAX_NEW, slots=SLOTS, greedy=True,
                        seed=seed)
    t_engine = time.perf_counter() - t0
    log(f"[engine] init + prefill/decode compile s={t_engine:.3f} "
        f"slots={SLOTS} pages={sess.engine.num_pages} "
        f"page_size={sess.engine.page_size}")

    questions = [e.question for e in corpus.examples]
    rids, walls = [], []
    for p in range(2):
        t0 = time.perf_counter()
        rids += serve(sess, questions[p * QUESTIONS:(p + 1) * QUESTIONS])
        walls.append(time.perf_counter() - t0)
    answers = [sess.requests[r].answer for r in rids]
    n_tok = sum(len(a.gen_tokens or ()) for a in answers)
    done = sum(sess.requests[r].state == "done" for r in rids)
    c = sess.counters
    log(f"[serve] requests={len(rids)} done={done} tokens={n_tok} "
        f"cold_pass_s={walls[0]:.3f} warm_pass_s={walls[1]:.3f} "
        f"engine_steps={sess.engine.steps}")
    log(f"[counters] retrieval_fallbacks={pipe.retrieval_fallbacks} "
        f"scr_fallbacks={pipe.scr_fallbacks} "
        f"retrieval_retries={c.retrieval_retries} failed={c.failed} "
        f"shed={c.shed_deadline + c.shed_overload + c.shed_oversize}"
        f"+{c.shed_slo} degraded={c.degraded}+{c.degraded_slo}")

    bad = check_session(pipe, sess, rids, gen_cfg.vocab_size)
    qvs = np.asarray(pipe.embed(questions), np.float32)
    got, ref_ids, ties = check_retrieval(pipe, qvs, answers)
    bad += got
    bad += check_scr(pipe, qvs, ref_ids)
    got, agree = check_logits(pipe, sess, answers)
    bad += got
    log(f"[check] retrieval, SCR and logits vs kernels/ref.py: "
        f"{'ok' if not bad else f'{len(bad)} failures'} "
        f"(retrieval near-ties passed: {ties}; prefill argmax == served "
        f"first token: {agree})")
    return bad


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = require_tpu()
    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1
    jax.monitoring.register_event_listener(count)

    t0 = time.perf_counter()
    bad = run_smoke(args.seed, DOCS, get_config("qwen25_0_5b"))
    stats = dev.memory_stats() or {}
    print(f"[device] {dev.device_kind} count={len(jax.devices())} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"total_s={time.perf_counter() - t0:.3f}")
    print(f"[cache] dir={cache_dir} hits={cache['hits']} "
          f"misses={cache['misses']}")
    if bad:
        for b in bad:
            print(f"[fail] {b}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
