"""Share of the expert-rows a MoE generator's FFN computes that its
tokens were routed to: the `moe_rows_routed` over the
`moe_rows_computed` attrs of the `engine/decode_step` and
`engine/prefill_chunk` spans begun in the window, summed. A dense
engine's spans, and a program without these attrs, give nothing to
read."""

SPANS = ("decode_step", "prefill_chunk")


def read(obs):
    lo, hi = obs.window
    routed = computed = 0
    for r in obs.records:
        if (r.comp == "engine" and r.name in SPANS and r.ph == "B"
                and lo <= r.ts < hi and "moe_rows_computed" in r.attrs):
            routed += r.attrs["moe_rows_routed"]
            computed += r.attrs["moe_rows_computed"]
    if not computed:
        return None
    return routed / computed * 100.0
