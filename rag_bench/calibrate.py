#!/usr/bin/env python3
"""The readings each correctness limit is set from: for each seed, one
short window of the cell at its own load, then the compared numbers of
the program and of the lower-precision control on the same evidence.

    python3 rag_bench/calibrate.py --workload <cell> --seconds 8 \
        --seeds 1 2 3 ...

One process: the index is loaded once, each seed builds its own
generator weights and session. Per seed it prints one JSON line with
the program's numbers, the control's, and two diagnostics read from the
program's own spans and paths:

- `hazards`: prefill chunks whose padded tail reaches past the pages the
  request mapped, while still inside its page-table row (the unmapped
  entries of a row point at pool page 0);
- `witness_gap`: for the sampled requests, how far each served token
  lies below the best token of the program's own non-paged bfloat16
  forward over the same prompt and served tokens (dense generators).

The benchmark's runs never run this; PERF.md records what it printed.
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


def hazards(records, engine) -> int:
    """Prefill chunks whose padded span ends in a row entry the request
    never mapped."""
    pages, n = {}, 0
    c, ps, w = engine.prefill_chunk, engine.page_size, engine.table_width
    for r in records:
        if r.comp != "engine":
            continue
        if r.name == "admitted":
            pages[r.rid] = int(r.attrs["pages"])
        elif r.name == "prefill_chunk" and r.ph == "B":
            last = (int(r.attrs["start"]) + c - 1) // ps
            if pages.get(r.rid, w) <= last < w:
                n += 1
    return n


def witness(stack, seqs) -> float:
    """Widest amount by which a served token lies below the best token of
    the program's own non-paged bfloat16 forward (dense generators)."""
    import jax.numpy as jnp
    from repro.models import dense, model
    eng = stack.sess.engine
    if eng.cfg.family != "dense":
        return float("nan")
    params = model.cast_params(eng.params, model.compute_dtype(eng.cfg))
    worst = 0.0
    for prompt, served in seqs:
        full = list(prompt) + list(served)
        toks = jnp.asarray(np.asarray(full[:-1], np.int32)[None])
        lg = np.asarray(dense.forward_logits(eng.cfg, params,
                                             {"tokens": toks}), np.float32)[0]
        rows = lg[len(prompt) - 1:]
        got = rows[np.arange(len(served)), np.asarray(served)]
        worst = max(worst, float((rows.max(-1) - got).max()))
    return worst


def page0(cell, seed: int) -> dict:
    """One prompt of the cell's traffic, served three times in a row by a
    fresh engine of the cell's configuration: first cold, then twice from
    the prefix cache. The answer length m is chosen from the mix so that
    the repeat's last prefill chunk, padded to the chunk size, reaches
    past the pages the request maps (m = ceil((p + 1) / 32) * 32 - p for
    prompt length p); then again with m + 32, where it does not. Greedy
    answers to one prompt are the same every time unless a served path is
    wrong; the reference's gaps say which."""
    from repro.serving.engine import ContinuousEngine
    from rag_bench import check, harness
    mix = cell.mix
    lo, hi = mix["answer_tokens"]["min"], mix["answer_tokens"]["max"]
    stack = harness.build(cell.config, mix, seed, trace=False)
    slm, eng = stack.slm, stack.sess.engine
    prompt = m = None
    for q in stack.questions:
        ans = stack.pipe.answer_batch([q])[0]
        p = slm.encode_prompt(ans.prompt, bucket=False)
        need = -(-(len(p) + 1) // 32) * 32 - len(p)
        if lo <= need and need + 32 <= hi:
            prompt, m = p, need
            break
    out = {"seed": seed, "prompt_tokens": len(prompt)}
    seqs = []
    for label, n in (("hazard", m), ("control", m + 32)):
        fresh = ContinuousEngine(eng.cfg, eng.params, slots=eng.slots,
                                 max_len=eng.max_len, page_size=eng.page_size)
        runs = [fresh.generate([prompt], max_new=n)[0].tokens
                for _ in range(3)]
        out[label] = {"answer_tokens": n,
                      "same_as_cold": [r == runs[0] for r in runs[1:]]}
        seqs += [([int(t) for t in prompt], list(r)) for r in runs]
        del fresh
    ev = check.Evidence(seqs, [], [], None, None, None,
                        harness.sub_seed(seed, "weights"),
                        slm.max_prompt + slm.max_new)
    harness.free(stack)
    gaps = [float(g.max()) for g, _ in check.model_gaps(ev, cell.config)]
    out["hazard"]["logit_gaps"] = gaps[:3]
    out["control"]["logit_gaps"] = gaps[3:]
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--page0", type=int, default=0,
                    help="run `page0` for each seed instead")
    args = ap.parse_args(argv)
    from rag_bench import check, harness
    from rag_bench.run import require_chips
    if args.page0:
        config, traffic_name = args.workload.split(".squad-")
        cell = harness.cell_from_files(args.workload, config,
                                       "squad-" + traffic_name, 1)
    else:
        cell = harness.load_cell(args.workload)
    require_chips(cell.chips)
    harness.configure_jax()
    for seed in args.seeds:
        if args.page0:
            print(json.dumps(page0(cell, seed)), flush=True)
            continue
        stack = harness.build(cell.config, cell.mix, seed, trace=True)
        harness.warm(stack, cell.config, cell.mix, seed)
        stop = harness.record_outputs(stack)
        log = harness.drive(stack, cell.mix, cell.params.get("rate_rps"),
                            args.seconds, seed)
        stop()
        ev = check.gather(stack, log, seed, harness.sub_seed(seed, "weights"),
                          int(cell.params["check_requests"]))
        row = {"seed": seed, "hazards": hazards(stack.sink.records(),
                                                stack.sess.engine),
               "checked_tokens": check.served_tokens(ev),
               "retrievals": len(ev.retrievals),
               "witness_gap": witness(stack, ev.sequences)}
        harness.free(stack)
        prog, ctrl = check.numbers(ev, cell.config, control=True)
        row.update(program=prog, control=ctrl,
                   correct=check.verdict(prog, cell.params["limits"])[0])
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
