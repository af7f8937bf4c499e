"""The whole serving step's share of the chip's bf16 peak
(observe.step_mfu), beside the rooflines that move `ttft_p95_ms`
(prefill_mfu, route_and_scan_roofline and scr_select_roofline): it
bounds what any one of them can add."""
from rag_bench import observe


def read(obs):
    return observe.step_mfu(obs)
