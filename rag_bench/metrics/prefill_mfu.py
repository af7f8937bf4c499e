"""Chunk-prefill FLOPs of the prompt tokens served in the traced span
(counts.prefill_chunk), over the device time of the chunk-prefill
program, as a share of the chip's bf16 peak."""


def read(obs):
    t = obs.device_time("prefill_chunk")
    w = obs.work.get("prefill_chunk")
    if not t or not w or not w.flops:
        return None
    return w.flops / t / obs.peaks["bf16_flops_per_s"] * 100.0
