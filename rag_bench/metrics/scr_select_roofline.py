"""The SCR select program's roofline share: the least time its counted
work needs (larger of FLOP and byte bounds, counts.scr_select) over its
device time."""

from rag_bench import counts


def read(obs):
    t = obs.device_time("scr_select")
    w = obs.work.get("scr_select")
    if not t or not w or not w.calls:
        return None
    return counts.roofline_time(w.flops, w.bytes, obs.peaks) / t * 100.0
