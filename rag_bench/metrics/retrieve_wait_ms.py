"""Mean wait of a session request for retrieval: from its session
`queued` record to the begin of the `session/retrieve` span whose `rids`
attr carries it, over the requests queued in the window. A wait that
spans the profiler's start or stop (`obs.traced`), whose calls stall the
serving loop for seconds, is left out."""


def read(obs):
    lo, hi = obs.window
    queued, waits = {}, []
    for r in obs.records:
        if r.comp != "session":
            continue
        if r.name == "queued" and lo <= r.ts < hi:
            queued[(r.src, r.rid)] = r.ts
        elif r.name == "retrieve" and r.ph == "B":
            for rid in r.attrs.get("rids", ()):
                t = queued.pop((r.src, rid), None)
                if t is not None and not any(t < m < r.ts
                                             for m in obs.traced):
                    waits.append(r.ts - t)
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e3
