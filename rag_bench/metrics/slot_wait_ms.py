"""Mean wait of an engine request for a slot: engine `admitted` less
engine `queued`, over the requests queued in the window, leaving out a
wait that spans the profiler's start or stop (`obs.traced`)."""


def read(obs):
    lo, hi = obs.window
    queued, waits = {}, []
    for r in obs.records:
        if r.comp != "engine":
            continue
        key = (r.src, r.rid)
        if r.name == "queued":
            queued.pop(key, None)
            if lo <= r.ts < hi:
                queued[key] = r.ts
        elif r.name == "admitted" and key in queued:
            t = queued.pop(key)
            if not any(t < m < r.ts for m in obs.traced):
                waits.append(r.ts - t)
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e3
