#!/usr/bin/env python3
"""Trace-invariant checker: the serving trace as a correctness oracle.

Validates a TraceSink record stream (in-process list, or a JSONL export
from `TraceSink.export_jsonl`) against the lifecycle contract documented
in docs/OBSERVABILITY.md:

  ordering    seq strictly increasing, ts monotone non-decreasing;
  lifecycle   per (comp, src, rid) the event DAG is respected —
              engine:  queued -> admitted -> prefill_chunk* ->
                       prefill_readback -> first_token -> token* ->
                       done | shed | cancelled
              session: queued -> retrieved -> condensed -> encode ->
                       done | shed | failed
              sched:   queued -> placed/requeue/hedge* -> done | shed
              with nothing after a terminal and at most one terminal;
  spans       every B has a matching E on the same (comp, src, rid)
              key, never re-opened, none left open at end of a complete
              trace, and only the known span names (SPAN_NAMES);
  tree        spans nest: a B's `parent` attr is the seq of the
              innermost span still open (-1 at the top), and no span
              closes while a span opened inside it is still open;
  links       an engine `queued` with `parent_src`/`parent_rid` names a
              session request queued before it;
  terminals   in a complete trace every request that entered a
              component reaches exactly one terminal state there —
              chaos may delay requests, never strand them;
  pager       page_stats snapshots are self-consistent (free <= total,
              retained <= mapped_refs) and a drained engine's mapped
              references are exactly its prefix-cache retentions;
  chaos       an injected replica crash that had requests in flight is
              followed by engine "cancelled" records on that replica —
              faults surface as span chains, not silent drops;
  replica     a sched "recover" requires an earlier "drain"/"probe" of
              the same replica.

Ring-buffer truncation is handled: when the export's first seq is > 0
the oldest records were evicted, and rids whose beginning fell off the
buffer are exempt from "must start with queued" (their remaining chain
is still order-checked).

Deliberately stdlib-only and repo-import-free so it runs over any JSONL
export with a bare python3 (CI artifact checks, post-mortems).

Usage: python tools/trace_check.py trace.jsonl [--live]
  --live   the trace is a running snapshot: skip completeness checks
           (unterminated requests and open spans are not violations)

Exit 0 and a per-component summary when clean; exit 1 listing every
violation otherwise.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

TERMINALS = {"engine": {"done", "shed", "cancelled"},
             "session": {"done", "shed", "failed"},
             "sched": {"done", "shed"}}
SPAN_NAMES = {("engine", "admit"), ("engine", "prefill_chunk"),
              ("engine", "prefill_readback"), ("engine", "decode_step"),
              ("engine", "decode_readback"), ("session", "step"),
              ("session", "retrieve"), ("session", "encode"),
              ("rag", "embed"), ("rag", "search"), ("rag", "scr"),
              ("rag", "prompt")}
# per-comp event -> prerequisites (any one suffices); "" = may be first
PREREQS = {
    "engine": {"queued": set(), "admitted": {"queued"},
               "prefill_chunk": {"admitted"},
               "prefill_readback": {"admitted"},
               "first_token": {"admitted"}, "token": {"first_token"},
               "done": {"first_token"}, "shed": {"queued"},
               "cancelled": {"queued"}},
    "session": {"queued": set(), "degraded": {"queued"},
                "retrieved": {"queued"}, "condensed": {"retrieved"},
                "encode": {"condensed"}, "done": {"condensed"},
                "failed": {"queued"}, "shed": {"queued"}},
    "sched": {"queued": set(), "degraded": {"queued"},
              "placed": {"queued"}, "requeue": {"placed"},
              "hedge": {"placed"}, "done": {"placed"},
              "shed": {"queued"}},
}


def _norm(rec) -> dict:
    """Accept TraceRecord objects or plain dicts."""
    if isinstance(rec, dict):
        return rec
    return rec.to_dict()


def load_jsonl(path) -> List[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


class TraceChecker:
    """One pass over a record stream, accumulating violations."""

    def __init__(self, records: Iterable, *, complete: bool = True):
        self.records = [_norm(r) for r in records]
        self.complete = complete
        self.violations: List[str] = []
        # first record's seq > 0 => ring buffer evicted the stream head
        self.truncated = bool(self.records) and self.records[0]["seq"] > 0

    def _bad(self, rec: Optional[dict], msg: str) -> None:
        where = f"seq={rec['seq']} " if rec else ""
        self.violations.append(where + msg)

    # ---------------------------------------------------------- ordering

    def _check_ordering(self) -> None:
        last_seq, last_ts = -1, float("-inf")
        for r in self.records:
            if r["seq"] <= last_seq:
                self._bad(r, f"seq not increasing (prev {last_seq})")
            if r["ts"] < last_ts:
                self._bad(r, f"ts went backwards (prev {last_ts:.9f})")
            last_seq, last_ts = r["seq"], r["ts"]

    # --------------------------------------------------------- lifecycle

    def _check_lifecycle(self) -> None:
        # (comp, src, rid) -> set of event names seen; and terminal name
        seen: Dict[Tuple, set] = defaultdict(set)
        term: Dict[Tuple, str] = {}
        grandfathered: set = set()
        for r in self.records:
            comp, rid = r["comp"], r["rid"]
            if comp not in PREREQS or rid < 0:
                continue
            key = (comp, r["src"], rid)
            name = r["name"]
            if r.get("ph") == "E":
                continue                  # E ordering is the span check's
            if key in term:
                if name == "queued":
                    # rid recycled (engine `generate` pins rids to batch
                    # index): a fresh queued starts a new incarnation
                    del term[key]
                    seen[key] = set()
                else:
                    self._bad(r, f"{key}: '{name}' after terminal "
                                 f"'{term[key]}'")
                    continue
            if key not in seen and name != "queued":
                if self.truncated:
                    grandfathered.add(key)
                else:
                    self._bad(r, f"{key}: first event '{name}', "
                                 f"expected 'queued'")
            prereq = PREREQS[comp].get(name)
            if prereq is None:
                self._bad(r, f"{key}: unknown event '{name}'")
            elif prereq and not (prereq & seen[key]) \
                    and key not in grandfathered:
                self._bad(r, f"{key}: '{name}' before any of "
                             f"{sorted(prereq)}")
            if name == "queued" and "queued" in seen[key]:
                self._bad(r, f"{key}: duplicate 'queued'")
            seen[key].add(name)
            if name in TERMINALS[comp]:
                term[key] = name
        if self.complete:
            for key, names in seen.items():
                if key not in term:
                    self._bad(None, f"{key}: no terminal state "
                                    f"(saw {sorted(names)})")

    # -------------------------------------------------------- span pairs

    def _check_spans(self) -> None:
        open_b: Dict[Tuple, int] = {}
        for r in self.records:
            if r.get("ph") not in ("B", "E"):
                continue
            key = (r["comp"], r["src"], r["rid"], r["name"])
            if (r["comp"], r["name"]) not in SPAN_NAMES:
                if r.get("ph") == "B":
                    self._bad(r, f"{key}: unknown span")
                continue
            if r.get("ph") == "B":
                if key in open_b:
                    self._bad(r, f"{key}: span re-opened (B at seq "
                                 f"{open_b[key]} still open)")
                open_b[key] = r["seq"]
            elif r.get("ph") == "E":
                if key not in open_b:
                    if not self.truncated:
                        self._bad(r, f"{key}: E without open B")
                else:
                    del open_b[key]
        if self.complete:
            for key, seq in open_b.items():
                self._bad(None, f"{key}: span opened at seq {seq} "
                                f"never closed")

    def _check_tree(self) -> None:
        first = self.records[0]["seq"] if self.records else 0
        stack: List[Tuple[Tuple, int]] = []      # open (key, B seq)
        for r in self.records:
            ph = r.get("ph")
            if ph not in ("B", "E"):
                continue
            key = (r["comp"], r["src"], r["rid"], r["name"])
            if ph == "B":
                parent = r["attrs"].get("parent")
                want = stack[-1][1] if stack else -1
                # a parent opened before a ring truncation is not here
                lost = self.truncated and not stack \
                    and parent is not None and parent < first
                if parent is not None and parent != want and not lost:
                    self._bad(r, f"{key}: parent {parent} is not the "
                                 f"innermost open span ({want})")
                stack.append((key, r["seq"]))
                continue
            at = [i for i, (k, _) in enumerate(stack) if k == key]
            if not at:
                continue                  # the span check reports it
            if at[-1] != len(stack) - 1:
                self._bad(r, f"{key}: closes before {stack[-1][0]} "
                             f"opened inside it")
            del stack[at[-1]]

    def _check_links(self) -> None:
        queued: set = set()
        for r in self.records:
            if r["name"] != "queued":
                continue
            if r["comp"] == "session":
                queued.add((r["src"], r["rid"]))
            a = r["attrs"]
            if r["comp"] == "engine" and "parent_src" in a \
                    and (a["parent_src"], a["parent_rid"]) not in queued \
                    and not self.truncated:
                self._bad(r, f"engine request {(r['src'], r['rid'])} "
                             f"links to session request "
                             f"{(a['parent_src'], a['parent_rid'])}, "
                             f"which was never queued")

    # ------------------------------------------------------------- pager

    def _check_pager(self) -> None:
        engine_seen: Dict[Tuple, set] = defaultdict(set)
        engine_term: set = set()
        last_stats: Dict[str, dict] = {}
        for r in self.records:
            if r["comp"] == "engine" and r["rid"] >= 0 \
                    and r.get("ph") != "E":
                key = (r["src"], r["rid"])
                if r["name"] == "queued":       # new incarnation
                    engine_term.discard(key)
                    engine_seen[key] = set()
                engine_seen[key].add(r["name"])
                if r["name"] in TERMINALS["engine"]:
                    engine_term.add(key)
            if r["comp"] != "pager":
                continue
            if r["name"] in ("prefix_hit", "cow_fork"):
                key = (r["src"], r["rid"])
                if "queued" not in engine_seen[key] \
                        and not self.truncated:
                    self._bad(r, f"pager '{r['name']}' for unknown "
                                 f"engine request {key}")
                if key in engine_term:
                    self._bad(r, f"pager '{r['name']}' after terminal "
                                 f"for {key}")
            elif r["name"] == "page_stats":
                a = r["attrs"]
                if a["free"] > a["total"]:
                    self._bad(r, f"page_stats: free {a['free']} > "
                                 f"total {a['total']}")
                if a["retained"] > a["mapped_refs"]:
                    self._bad(r, f"page_stats: retained {a['retained']}"
                                 f" > mapped_refs {a['mapped_refs']}")
                last_stats[r["src"]] = a
        for src, a in last_stats.items():
            if a.get("inflight", 0) == 0 \
                    and a["mapped_refs"] != a["retained"]:
                self._bad(None, f"src={src}: drained engine holds "
                                f"{a['mapped_refs']} refs but only "
                                f"{a['retained']} retentions — leak")

    # ------------------------------------------------------------- chaos

    def _check_chaos(self) -> None:
        for i, r in enumerate(self.records):
            if r["comp"] != "chaos" or r["name"] != "injected":
                continue
            a = r["attrs"]
            if "kind" not in a:
                self._bad(r, "chaos record without fault kind")
                continue
            if a["kind"] == "replica_crash" and a.get("inflight", 0) > 0:
                # a crash loses in-flight state: the wrapped engine must
                # surface it as cancelled chains, never a silent drop
                ok = any(x["comp"] == "engine"
                         and x["name"] == "cancelled"
                         and x["src"] == r["src"]
                         for x in self.records[i + 1:])
                if not ok:
                    self._bad(r, f"crash on src={r['src']} with "
                                 f"{a['inflight']} in flight but no "
                                 f"'cancelled' records follow")

    def _check_replica(self) -> None:
        drained: set = set()
        for r in self.records:
            if r["comp"] != "sched" or r["rid"] >= 0:
                continue
            rep = r["attrs"].get("replica")
            if r["name"] in ("drain", "probe"):
                drained.add((r["src"], rep))
            elif r["name"] == "recover" \
                    and (r["src"], rep) not in drained \
                    and not self.truncated:
                self._bad(r, f"replica {rep} recovered without an "
                             f"earlier drain/probe")

    # --------------------------------------------------------------- run

    def run(self) -> List[str]:
        self._check_ordering()
        self._check_lifecycle()
        self._check_spans()
        self._check_tree()
        self._check_links()
        self._check_pager()
        self._check_chaos()
        self._check_replica()
        return self.violations

    def summary(self) -> Dict[str, int]:
        out: Dict[str, int] = defaultdict(int)
        for r in self.records:
            out[r["comp"]] += 1
        out["records"] = len(self.records)
        out["violations"] = len(self.violations)
        return dict(out)


def check_records(records: Iterable, *, complete: bool = True) -> List[str]:
    """Violations in a record stream (TraceRecords or dicts); [] = clean."""
    return TraceChecker(records, complete=complete).run()


def check_jsonl(path, *, complete: bool = True) -> List[str]:
    """Violations in a `TraceSink.export_jsonl` file; [] = clean."""
    return check_records(load_jsonl(path), complete=complete)


def last_page_stats(records: Iterable, src: Optional[str] = None) -> dict:
    """The final page_stats snapshot (for reconciling an export against
    a live engine's `page_stats()`)."""
    out: dict = {}
    for r in (_norm(x) for x in records):
        if r["comp"] == "pager" and r["name"] == "page_stats" \
                and (src is None or r["src"] == src):
            out = r["attrs"]
    return out


def main(argv: List[str]) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    live = "--live" in argv
    path = [a for a in argv if not a.startswith("--")][0]
    checker = TraceChecker(load_jsonl(path), complete=not live)
    violations = checker.run()
    s = checker.summary()
    if violations:
        for v in violations:
            print(f"VIOLATION: {v}")
        print(f"{len(violations)} violation(s) in {s['records']} records")
        return 1
    comps = ", ".join(f"{k}={v}" for k, v in sorted(s.items())
                      if k not in ("records", "violations"))
    print(f"trace OK: {s['records']} records ({comps})"
          + (" [truncated head]" if checker.truncated else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
