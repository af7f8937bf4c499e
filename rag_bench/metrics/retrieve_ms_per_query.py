"""Host time of the session's retrieve stage per query retrieved: the sum
of `session/retrieve` span durations (embed, route_and_scan, scr_select,
prompt assembly, host arrays back) over the queries they carried."""


def read(obs):
    spans = obs.spans("session", "retrieve")
    n = sum(int(a.get("n", 0)) for _, _, a in spans)
    if not n:
        return None
    return sum(e - b for b, e, _ in spans) / n * 1e3
