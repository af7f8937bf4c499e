"""Chunked prefill as a request lives it: engine `first_token` less
engine `admitted` (its chunks, the decode steps between them and the
readback of its last chunk's logits), mean over the requests queued in
the window, leaving out one that spans the profiler's start or stop
(`obs.traced`). `GenResult.prefill_s` is the same interval."""


def read(obs):
    lo, hi = obs.window
    in_window, admitted, out = {}, {}, []
    for r in obs.records:
        if r.comp != "engine":
            continue
        key = (r.src, r.rid)
        if r.name == "queued":
            in_window[key] = lo <= r.ts < hi
            admitted.pop(key, None)
        elif r.name == "admitted" and in_window.get(key):
            admitted[key] = r.ts
        elif r.name == "first_token" and key in admitted:
            t = admitted.pop(key)
            if not any(t < m < r.ts for m in obs.traced):
                out.append(r.ts - t)
    if not out:
        return None
    return sum(out) / len(out) * 1e3
