"""Reduce a profiler trace (`.xplane.pb`) to device busy time, device time
per program, and idle gaps attributed to what the host was doing.

The layout read is the TPU's: each device plane (`/device:TPU:<n>`) has
a line "XLA Modules", one event per program execution, named
`<module>(<fingerprint>)` (so two programs under one module name stay
apart), and a line "XLA Ops", one event per operation, named by its HLO
text. An operation belongs to the program execution whose span holds its
start. Busy time is the union of the operations' spans (the long
"Async XLA Ops" copies are left out). Host annotations are
`jax.profiler.TraceAnnotation` spans on the host plane, named by the
harness ("session step", "load generator").
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

HOST_LABELS = ("session step", "load generator")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclass
class Program:
    module: str
    program_id: str
    time_s: float = 0.0
    runs: int = 0
    ops: Dict[str, float] = field(default_factory=dict)


@dataclass
class Reduced:
    window_s: float                   # traced span, first to last event
    busy_s: float                     # union of op intervals, mean over chips
    chips: int
    programs: List[Program]
    idle_by_host: Dict[str, float]    # idle device seconds by host activity

    def by_module(self, module: str) -> List[Program]:
        return [p for p in self.programs if p.module == module]


def _stats(ev) -> dict:
    return dict(ev.stats)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _overlap(a: float, b: float, spans: List[Tuple[float, float]]) -> float:
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in spans)


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def split_module(name: str) -> Tuple[str, str]:
    """`jit_f(123)` -> ("jit_f", "123")."""
    if name.endswith(")") and "(" in name:
        i = name.rindex("(")
        return name[:i], name[i + 1:-1]
    return name, ""


def op_name(text: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> "fusion.12"."""
    return text.split(" = ", 1)[0].lstrip("%")


def reduce_profile(pd, span_ns: Optional[Tuple[float, float]] = None
                   ) -> Reduced:
    """Reduce a `jax.profiler.ProfileData`. `span_ns` bounds the window
    (default: first to last device operation)."""
    progs: Dict[Tuple[str, str], Program] = {}
    busy_per_chip: List[List[Tuple[float, float]]] = []
    host: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        if not is_device_plane(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_LABELS:
                        host[ev.name].append((ev.start_ns, ev.end_ns))
            continue
        lines = {line.name: line for line in plane.lines}
        runs = []                      # (start, end, program key)
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                key = split_module(ev.name)
                p = progs.setdefault(key, Program(*key))
                p.time_s += ev.duration_ns * 1e-9
                p.runs += 1
                runs.append((ev.start_ns, ev.end_ns, key))
        runs.sort()
        starts = [r[0] for r in runs]
        ops = []
        if OPS_LINE in lines:
            ops = sorted(((ev.start_ns, ev.end_ns, ev.name)
                          for ev in lines[OPS_LINE].events),
                         key=lambda o: (o[0], -o[1]))
        for (a, b, name), own in zip(ops, _self_times(ops)):
            lo, hi = min(lo, a), max(hi, b)
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < runs[i][1]:
                p = progs[runs[i][2]]
                name = op_name(name)
                p.ops[name] = p.ops.get(name, 0.0) + own * 1e-9
        busy_per_chip.append(_union([(a, b) for a, b, _ in ops]))
    if span_ns is not None:
        lo, hi = span_ns
    chips = max(1, len(busy_per_chip))
    busy = [_clip(u, lo, hi) for u in busy_per_chip] or [[]]
    busy_s = sum(sum(b - a for a, b in u) for u in busy) / chips * 1e-9
    idle = _idle_by_host(busy[0], lo, hi, host) if hi > lo else {}
    return Reduced(window_s=max(0.0, (hi - lo) * 1e-9), busy_s=busy_s,
                   chips=chips, programs=sorted(
                       progs.values(), key=lambda p: -p.time_s),
                   idle_by_host=idle)


def _self_times(ops: List[Tuple[float, float, str]]) -> List[float]:
    """Each operation's duration less that of the operations nested in it
    (a loop op's span holds its body's ops), for ops sorted by start and
    then by end, latest first."""
    own = [b - a for a, b, _ in ops]
    stack: List[int] = []
    for i, (a, b, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][1]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return [max(0.0, x) for x in own]


def _clip(spans, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi]


def _idle_by_host(busy, lo, hi, host) -> Dict[str, float]:
    """Split the device's idle time in [lo, hi] by which host annotation
    was open; time under none is "host, outside any annotation"."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = {k: _union(v) for k, v in host.items()}
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        left = b - a
        for name, sp in spans.items():
            o = _overlap(a, b, sp)
            out[name] += o * 1e-9
            left -= o
        out["host, outside any annotation"] += max(0.0, left) * 1e-9
    return dict(out)


def load(trace_dir: str):
    """The newest `.xplane.pb` under `trace_dir`, read."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def top_ops(red: Reduced, names: Dict[Tuple[str, str], str],
            n: int = 10) -> List[list]:
    """The device operations that took most time, as
    [["<program>/<op>", seconds], ...]."""
    rows = []
    for p in red.programs:
        label = names.get((p.module, p.program_id), p.module)
        rows += [[f"{label}/{op}", t] for op, t in p.ops.items()]
    return sorted(rows, key=lambda r: -r[1])[:n]


def idle_gaps(red: Reduced, n: int = 10) -> List[list]:
    return sorted(([k, v] for k, v in red.idle_by_host.items()),
                  key=lambda r: -r[1])[:n]


def assign_roles(red: Reduced, expected: Dict[str, Tuple[str, int]]
                 ) -> Dict[Tuple[str, str], str]:
    """Name each program by role. `expected` maps a role to (module
    name, executions counted by the program's own spans in the traced
    window). A role that alone lowers to its module takes every program
    of that name (one per shape), if their executions add up to its
    count. Roles that share a module name (the engine's jitted lambdas
    all lower to `jit__lambda`) each take the one program whose
    executions equal the role's count, if exactly one does. Any other
    role is left without a program, so that its metrics read nothing
    rather than another program's time."""
    out: Dict[Tuple[str, str], str] = {}
    by_mod: Dict[str, List[str]] = defaultdict(list)
    for role, (mod, _) in expected.items():
        by_mod[mod].append(role)
    for mod, roles in by_mod.items():
        progs = red.by_module(mod)
        if len(roles) == 1:
            r = roles[0]
            if progs and sum(p.runs for p in progs) == expected[r][1]:
                out.update({(p.module, p.program_id): r for p in progs})
            continue
        for r in roles:
            hits = [p for p in progs if p.runs == expected[r][1]]
            if len(hits) == 1:
                out[(hits[0].module, hits[0].program_id)] = r
    return out


def role_time(red: Reduced, roles: Dict[Tuple[str, str], str], role: str
              ) -> Optional[float]:
    t = [p.time_s for p in red.programs
         if roles.get((p.module, p.program_id)) == role]
    return sum(t) if t else None


def role_runs(red: Reduced, roles: Dict[Tuple[str, str], str], role: str
              ) -> int:
    return sum(p.runs for p in red.programs
               if roles.get((p.module, p.program_id)) == role)


def describe(pd, events: int = 2) -> Iterable[str]:
    """Plane and line names with their event counts and a few events'
    stats, for a first look at an unfamiliar trace."""
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            yield f"{plane.name} | {line.name} | {len(evs)} events"
            for ev in evs[:events]:
                yield f"    {ev.name} {ev.duration_ns}ns {_stats(ev)}"
