"""A tiny copy of a cell for CPU tests: the reduced generator, a small
corpus and a short window, written as a BENCHMARK.json and the files it
names under a scratch directory. Nothing here runs on the chip."""
from __future__ import annotations

import json
from pathlib import Path

TINY_MODEL = {
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 256, "vocab_size": 512,
    "tie_word_embeddings": True, "rope_theta": 1000000.0, "head_dim": 32,
    "qkv_bias": True,
}

TINY_LIMITS = {"logit_gap": 0.05, "retrieval_err": 1e-4, "scr_err": 1e-4}


def write(root: Path, *, loop: str = "open") -> Path:
    """Lay out a one-cell benchmark under `root`; returns its
    BENCHMARK.json. The configuration names the full-size arch; tests
    hand the harness its reduced program config."""
    root.mkdir(parents=True, exist_ok=True)
    bench_dir = root / "rag_bench"
    for sub in ("configs", "traffic", "cells"):
        (bench_dir / sub).mkdir(parents=True, exist_ok=True)
    conf = {"name": "tiny", "arch": "qwen25_0_5b", "model": TINY_MODEL,
            "corpus": {"style": "squad", "docs": 96, "questions": 48,
                       "data_seed": 7},
            "retrieval": {"embed_dim": 64, "top_k": 3, "n_probe": 2},
            "engine": {"slots": 4, "page_size": 32, "max_prompt": 256,
                       "retrieve_chunk": 2}}
    mix = {"answer_tokens": {"dist": "lognormal", "median": 36,
                             "sigma": 0.2, "min": 32, "max": 48}}
    if loop == "open":
        mix.update(loop="open", arrivals="poisson")
    else:
        mix.update(loop="closed", clients=4, pool=64)
    cell = {"rate_rps": 3.0, "check_requests": 64, "limits": TINY_LIMITS}
    files = {"configs/tiny.json": conf, "traffic/mix.json": mix,
             "cells/tiny.mix.json": cell}
    for rel, obj in files.items():
        (bench_dir / rel).write_text(json.dumps(obj))
    bench = {
        "configs": [{"name": "tiny", "file": "rag_bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny",
                       "traffic": "mix", "chips": 1}],
        "end_to_end": [{"name": n, "unit": u} for n, u in (
            ("ttft_p50_ms", "ms"), ("ttft_p95_ms", "ms"),
            ("itl_p95_ms", "ms"), ("tokens_per_s", "tokens/s"),
            ("setup_s", "s"))],
        "per_layer": [{"name": n, "unit": u} for n, u in (
            ("retrieve_ms_per_query", "ms"), ("decode_step_ms", "ms"),
            ("prefill_mfu", "%"), ("decode_hbm_roofline", "%"),
            ("route_and_scan_roofline", "%"), ("scr_select_roofline", "%"),
            ("device_idle_share", "%"), ("step_mfu.ttft", "%"),
            ("step_mfu.itl", "%"))],
    }
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path
