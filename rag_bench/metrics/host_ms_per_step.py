"""Host time of a session step: a `session/step` span less the
`engine/decode_readback` and `engine/prefill_readback` spans inside it
(where the host waits for the device), mean over the steps begun in the
window, leaving out one that spans the profiler's start or stop
(`obs.traced`)."""

READBACKS = ("decode_readback", "prefill_readback")


def read(obs):
    lo, hi = obs.window
    step = None                  # [begin ts, readback seconds inside]
    open_b, out = {}, []
    for r in obs.records:
        if r.ph not in ("B", "E"):
            continue
        if r.comp == "session" and r.name == "step":
            if r.ph == "B":
                step = [r.ts, 0.0] if lo <= r.ts < hi else None
            elif step is not None:
                if not any(step[0] < m < r.ts for m in obs.traced):
                    out.append(r.ts - step[0] - step[1])
                step = None
        elif r.comp == "engine" and r.name in READBACKS and step is not None:
            key = (r.src, r.rid, r.name)
            if r.ph == "B":
                open_b[key] = r.ts
            elif key in open_b:
                step[1] += r.ts - open_b.pop(key)
    if not out:
        return None
    return sum(out) / len(out) * 1e3
