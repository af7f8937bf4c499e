"""The comparison that decides `correct`.

It covers the three layers each cell's traffic runs through, on what the
timed path itself produced at the timed sizes:

- retrieval: every `route_and_scan` answer recorded in the window,
  against a float64 routing and scan over the index's stored vectors;
- SCR: every `scr_select` answer recorded in the window, against float64
  window scores;
- the generator: a sample, drawn from the seed, of `check_requests` of
  the requests the window finished, the longest among them (some
  hundreds of served tokens). The float32 reference runs once over each
  prompt with its
  served tokens; a served token's gap is how far its reference logit lies
  below the reference's best at that position (greedy decoding serves
  the program's own best).

Each number is held to the limit in the cell's file (`cells/<cell>.json`);
PERF.md gives the readings each limit was set from.
"""
from __future__ import annotations

import gc
import math
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from rag_bench import reference, traffic

NUMBERS = ("logit_gap", "retrieval_err", "scr_err")


@dataclass
class Evidence:
    """Host copies of everything the checks compare, taken before the
    program's state is freed."""
    sequences: List[Tuple[List[int], List[int]]]   # (prompt ids, served)
    retrievals: list
    selects: list
    index: reference.IndexData
    windows: np.ndarray
    window_lens: np.ndarray
    weight_seed: int
    length: int


def sample_requests(done: Dict[int, List[int]], seed: int, n: int
                    ) -> List[int]:
    """`n` request ids to check: the one that served the most tokens,
    then others drawn from the seed. A fixed count keeps the reference's
    batch, and so its compiled program, the same in every run."""
    if not done:
        return []
    rids = sorted(done)
    longest = max(rids, key=lambda r: (len(done[r]), -r))
    rest = [r for r in rids if r != longest]
    order = traffic.rng_for(seed, "check").permutation(len(rest))
    return [longest] + [rest[i] for i in order[:n - 1]]


def gather(stack, log, seed: int, weight_seed: int,
           n_requests: int) -> Evidence:
    """What the window produced, read back to the host: the sampled
    requests' prompt ids and served tokens, the recorded retrieval and SCR
    answers, and the index's stored vectors."""
    sess, slm, pipe = stack.sess, stack.slm, stack.pipe
    served = {}
    for rid, r in log.reqs.items():
        req = sess.requests[rid]
        if (log.start <= r.due < log.end and req.state == "done"
                and req.answer.gen_tokens):
            served[rid] = list(req.answer.gen_tokens)
    seqs = []
    for rid in sample_requests(served, seed, n_requests):
        prompt = slm.encode_prompt(sess.requests[rid].answer.prompt,
                                   bucket=False)
        seqs.append(([int(t) for t in prompt], served[rid]))
    rows, lens, slot_ids, _ = pipe.index.device_pack()
    index = reference.IndexData(np.asarray(pipe.index.centroids, np.float32),
                                np.asarray(rows), np.asarray(lens),
                                np.asarray(slot_ids))
    windows, wlens = pipe.window_index.pack()
    calls = [c[1:] for c in stack.retrievals]
    sels = [c[1:] for c in stack.selects]
    return Evidence(seqs, calls, sels, index, np.array(windows),
                    np.array(wlens), weight_seed,
                    slm.max_prompt + slm.max_new)


def model_gaps(ev: Evidence, conf: dict, control: bool = False):
    """Per sampled request, (served tokens' gaps, the fp8 control's gaps
    at the same positions); without `control` the two are the same."""
    import jax.numpy as jnp
    from rag_bench import weights
    arch = reference.Arch.from_config(conf)
    gen = reference.Generator(arch, weights.make(arch, ev.weight_seed,
                                                 jnp.float32))
    out = gen.gaps(ev.sequences, ev.length, control=control)
    del gen
    gc.collect()
    return out


def numbers(ev: Evidence, conf: dict, control: bool = False):
    """The cell's compared numbers from the evidence of one run, and with
    `control` the same numbers with the lower-precision control in the
    program's place (fp8 for the generator's bfloat16, bfloat16 for the
    kernels' float32); else None in its place."""
    pairs = model_gaps(ev, conf, control=control)

    def widest(i):
        return float(max((p[i].max() for p in pairs if len(p[i])),
                         default=math.inf))
    prog = {"logit_gap": widest(0)}
    prog.update(reference.retrieval_numbers(ev.index, ev.retrievals))
    prog.update(reference.scr_numbers(ev.windows, ev.window_lens, ev.selects))
    if not control:
        return prog, None
    ctrl = {"logit_gap": widest(1)}
    ctrl.update(reference.retrieval_numbers(
        ev.index, reference.control_retrievals(ev.index, ev.retrievals)))
    ctrl.update(reference.scr_numbers(
        ev.windows, ev.window_lens,
        reference.control_selects(ev.windows, ev.window_lens, ev.selects)))
    return prog, ctrl


def verdict(nums: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, dict]]:
    """Correct when every number is at or under its limit (a missing or
    non-finite number fails)."""
    shown = {}
    ok = True
    for name in NUMBERS:
        v = nums.get(name, math.inf)
        lim = limits[name]
        good = math.isfinite(v) and v <= lim
        ok &= good
        shown[name] = {"value": v, "limit": lim}
    return ok, shown


def served_tokens(ev: Evidence) -> int:
    return sum(len(s) for _, s in ev.sequences)
