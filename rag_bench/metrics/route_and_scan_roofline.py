"""The fused route-and-scan program's roofline share: the least time its
counted work needs (larger of FLOP and byte bounds, counts.route_and_scan)
over its device time."""

from rag_bench import counts


def read(obs):
    t = obs.device_time("route_and_scan")
    w = obs.work.get("route_and_scan")
    if not t or not w or not w.calls:
        return None
    return counts.roofline_time(w.flops, w.bytes, obs.peaks) / t * 100.0
