"""What a traced run observed, in the form the per-layer metric readers
(`metrics/<name>.py`) take: the program's spans and counters, the device
trace reduced to programs, and the algorithmic counts of the work served
inside the traced span.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from rag_bench import counts, reference, trace_reduce

# role -> XLA module name the program lowers to
MODULES = {"decode": "jit__lambda", "prefill_chunk": "jit__lambda",
           "route_and_scan": "jit_route_and_scan",
           "scr_select": "jit_scr_select"}


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0
    calls: int = 0

    def add(self, fb) -> None:
        self.flops += fb[0]
        self.bytes += fb[1]
        self.calls += 1


@dataclass
class Observed:
    """Input of every per-layer metric reader."""
    records: list                          # TraceSink records, whole window
    window: Tuple[float, float]            # perf_counter span of the window
    traced: Tuple[float, float]            # perf_counter span of the trace
    device: trace_reduce.Reduced
    roles: Dict[Tuple[str, str], str]
    work: Dict[str, Work]                  # role -> counted work, traced span
    peaks: dict
    extra: Dict[str, float] = field(default_factory=dict)

    def device_time(self, role: str) -> Optional[float]:
        return trace_reduce.role_time(self.device, self.roles, role)

    def spans(self, comp: str, name: str, within=None) -> List[tuple]:
        """(begin ts, end ts, begin attrs) of completed spans whose begin
        lies in `within` (default: the window)."""
        lo, hi = within or self.window
        open_b, out = {}, []
        for r in self.records:
            if r.comp != comp or r.name != name:
                continue
            key = (r.src, r.rid)
            if r.ph == "B":
                open_b[key] = r
            elif r.ph == "E" and key in open_b:
                b = open_b.pop(key)
                if lo <= b.ts < hi:
                    out.append((b.ts, r.ts, b.attrs))
        return out


def engine_work(records, span, m: counts.Model) -> Dict[str, Work]:
    """Prefill-chunk and decode work inside `span`, rebuilt from the
    engine's records: each request's prompt length (queued), the tokens it
    has emitted (first_token, token), its chunks (prefill_chunk begin:
    start, n) and the decode steps it was active in."""
    lo, hi = span
    plen: Dict[Tuple[str, int], int] = {}
    ntok: Dict[Tuple[str, int], int] = {}
    active: Dict[str, set] = {}
    work = {"prefill_chunk": Work(), "decode": Work()}
    for r in records:
        if r.comp != "engine":
            continue
        key = (r.src, r.rid)
        if r.name == "queued":
            plen[key] = int(r.attrs["prompt_len"])
        elif r.name == "first_token":
            ntok[key] = 1
            active.setdefault(r.src, set()).add(key)
        elif r.name == "token":
            ntok[key] = ntok.get(key, 0) + 1
        elif r.name in ("done", "cancelled", "shed"):
            active.get(r.src, set()).discard(key)
        elif r.ph != "B" or not lo <= r.ts < hi:
            continue
        elif r.name == "prefill_chunk":
            start, n = int(r.attrs["start"]), int(r.attrs["n"])
            work["prefill_chunk"].add(counts.prefill_chunk(
                m, start, n, start + n >= plen[key]))
        elif r.name == "decode_step":
            lens = [plen[k] + ntok[k] for k in active.get(r.src, ())]
            work["decode"].add(counts.decode_step(m, lens))
    return work


def kernel_work(retrievals, selects, span, ix: reference.IndexData,
                wlens: np.ndarray) -> Dict[str, Work]:
    """route_and_scan and scr_select work of the calls recorded inside
    `span`. The probed clusters are those the float64 reference routes
    to."""
    lo, hi = span
    d = ix.rows.shape[2]
    nc = len(ix.lens)
    work = {"route_and_scan": Work(), "scr_select": Work()}
    for t, q, _ids, _d, _k, n_probe in retrievals:
        if not lo <= t < hi:
            continue
        probes = [reference.route(ix, q[b], n_probe) for b in range(len(q))]
        per_q = [int(ix.lens[p].sum()) for p in probes]
        distinct = int(ix.lens[np.unique(np.concatenate(probes))].sum())
        work["route_and_scan"].add(counts.route_and_scan(
            d, nc, len(q), per_q, distinct))
    for t, q, doc_ids, _s, _w in selects:
        if not lo <= t < hi:
            continue
        valid = doc_ids[doc_ids >= 0]
        pairs = [int(wlens[i]) for i in valid]
        distinct = int(wlens[np.unique(valid)].sum()) if len(valid) else 0
        work["scr_select"].add(counts.scr_select(q.shape[1], len(q), pairs,
                                                 distinct))
    return work


def step_mfu(obs: Observed) -> Optional[float]:
    """Every counted FLOP served in the traced span (chunk prefill,
    decode, route_and_scan, scr_select) over the span's length, as a
    share of the chip's bf16 peak."""
    flops = sum(w.flops for w in obs.work.values())
    if not flops:
        return None
    return (flops / obs.extra["window_s"] / obs.peaks["bf16_flops_per_s"]
            * 100.0)
